"""Fast Approximate Gaussian Process (FAGP) — the paper's core technique.

GP regression with a *decomposed kernel* (paper Eqs. 8-12): the N x N
kernel inverse is replaced, via the Woodbury identity, by the inverse of
the M x M matrix

    Lbar = Lambda^{-1} + Phi^T Sigma_n^{-1} Phi          (M = feature count)

Public API (one self-describing session; see also ``core.gp.GP``):

    spec  = GPSpec.create(n=8, eps=[0.8, 0.8], noise=0.05)   # one frozen spec
    state = fit(X, y, spec)          # spec is baked into the state
    mu, var = predict_mean_var(state, Xs)   # nothing re-passed — ever
    state = fit_update(state, Xn, yn)
    loss = nlml(X, y, spec)

``GPSpec`` merges the kernel hyperparameters (differentiable data leaves:
``eps``/``rho``/``noise``, plus the RFF spectral draws ``omega``) with the
static expansion choices (hashable metadata, trigger recompilation when
changed).  ``fit`` bakes the spec into ``FAGPState``, so
``predict``/``fit_update``/``predict_mean_var`` derive the feature map,
backend and block size from the state — a caller can no longer fit with
``n=12`` and predict with ``n=10`` and silently get wrong features.
``state.with_spec(...)`` is the explicit escape hatch for swapping the
execution knobs (backend, block size) at serve time; structural changes
(expansion, n, index set, hyperparameters) are rejected because they are
frozen into the factorization.

The kernel decomposition itself is PLUGGABLE (``core.expansions``): the
spec names a registered :class:`~repro.core.expansions.KernelExpansion`
(``spec.expansion``), which supplies the static index table (its row count
IS M), the log weights, the jnp feature map, and the in-VMEM Pallas tile
builder.  ``hermite`` (the paper's Mercer eigen-expansion of the SE
kernel) is the default; ``rff_se`` and ``rff_matern52`` (random Fourier
features, spectral draws carried as spec data) ship as the second family —
every entry point below, both distributed schedules, and the bank are
expansion-generic.

Targets ``y`` may be ``(N,)`` or multi-output ``(N, T)``: all T tasks share
the one M x M Cholesky factorization (the expensive part) and get per-task
mean weights ``u`` of shape ``(M, T)`` from one batched triangular solve —
fitting T tasks costs one fit plus T - 1 extra GEMV-sized solves.

Two mathematically identical posterior evaluation paths are provided:

* ``mode="paper"`` — the literal GEMM chain of Eqs. 11-12, in the paper's
  operation order (forms the N x N approximate inverse, then W = N* x N).
  This is the *faithful baseline*: it is what cuFAGP times on the GPU.

* ``mode="fused"`` — beyond-paper algebraic simplification.  Substituting
  Lbar into Eqs. 11-12 collapses them to the weight-space form

      mu*    = Phi* u,            u = Lbar^{-1} Phi^T y / sigma^2
      Sigma* = Phi* Lbar^{-1} Phi*^T

  which avoids every N x N / N* x N intermediate (O(N M) -> O(M^2) memory,
  and ~N/M fewer FLOPs for the covariance).  Tests assert the two modes
  agree to f32 tolerance; EXPERIMENTS.md §Perf reports them separately.

Both paths share ``fit``, which accumulates the two sufficient statistics
G = Phi^T Phi and b = Phi^T y in one streaming pass — constant memory in N
(beyond-paper; the paper materializes Phi whole).  Execution is dispatched
through a registry of capability-declaring backends (``register_backend``
/ ``get_backend``); each backend implements fit/features/mean_var/moments
and declares what it ``supports`` so unsupported specs are refused with a
clear error up front instead of crashing deep inside kernel preparation:

* ``backend="jnp"``    — scan over row blocks, pure XLA (any device);
* ``backend="pallas"`` — the streaming fused-fit kernel
  (``kernels/phi_gram``): feature tiles are generated in VMEM inside the
  Gram accumulation by the expansion's tile builder, so Phi is never
  written to HBM — for ANY registered expansion.

The same registry serves ``predict_mean_var`` and the per-shard moment
accumulation in ``core.distributed``.  ``fit_update`` absorbs new
observations into a fitted state by a rank-k Cholesky update of B —
O(k M^2), no pass over the original N rows (the serving ingest path).

Numerical form (beyond-paper, required for f32): Mercer lambda_n decays
geometrically and underflows f32 by column ~40, so Lbar = Lambda^{-1} + ...
cannot be formed directly.  We solve the symmetrically-scaled system

    B = I + D G D / sigma^2,      D = diag(sqrt(lambda))  (log-space)

assembled in exactly one place (``_assemble_scaled_system``) and shared by
fit, nlml and the distributed schedules, with Lbar^{-1} = D B^{-1} D and
logdet(Lbar) + logdet(Lambda) = logdet(B).  B has unit diagonal plus a PSD
term (cond(B) bounded by 1 + ||DGD||/sig^2), and columns whose sqrt(lambda)
underflows contribute an identity row — numerically inert, exactly as they
should be.  (RFF weights are flat 1/R — the same scaled form degrades
gracefully to a plain normalized Gram.)

REMOVED (was deprecated for two releases): the split ``fit(X, y, params,
cfg)`` / ``predict(state, Xs, cfg)`` / ``nlml(X, y, params, idx, n_max)``
signatures that re-took configuration at every call site now raise
``TypeError``.  See README §Migration.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .approximation import (
    Approximation,
    UnsupportedError,
    get_approximation,
    register_approximation,
)
from .expansions import (
    available_expansions,
    get_expansion,
)
from .mercer import (
    IndexSetKind,
    SEKernelParams,
    make_index_set,
)

__all__ = [
    "FAGPConfig",
    "FAGPState",
    "FitBackend",
    "GPSpec",
    "available_backends",
    "available_expansions",
    "build_features",
    "fit",
    "fit_update",
    "get_backend",
    "get_expansion",
    "nlml",
    "predict",
    "predict_mean_var",
    "register_backend",
]

# Every f32 product of the model math runs at full f32 precision.  A TPU's
# default for an f32 matmul rounds its inputs to bfloat16, and B's
# conditioning (entries near N*lambda_0/sigma^2) amplifies that error.
_HIGHEST = jax.lax.Precision.HIGHEST


def _removed(old: str, new: str) -> None:
    raise TypeError(
        f"{old} was removed (deprecated two releases ago); {new}"
    )


@dataclasses.dataclass(frozen=True)
class FAGPConfig:
    """Static configuration of the Hermite-Mercer expansion.

    Retained as the static half of the legacy split API (workload tables in
    ``configs/fagp.py`` carry it without hyperparameters); it describes the
    ``hermite`` expansion only.  New code constructs a ``GPSpec`` and never
    passes an ``FAGPConfig`` to the fit / predict entry points — those
    signatures were removed this release.

    n:          eigenvalues per input dimension (paper's n).
    index_set:  'full' (paper; M = n^p) | 'total_degree' | 'hyperbolic_cross'.
    degree:     truncation parameter for the non-full sets (None = auto).
    block_rows: row-block size for the streaming Gram accumulation.
    store_train: keep (Phi, y) in the state — required for mode='paper'
                 prediction and for the cross-covariance term of Eq. 12.
    """

    n: int
    index_set: IndexSetKind = "full"
    degree: Optional[int] = None
    block_rows: int = 4096
    store_train: bool = True
    backend: str = "jnp"  # 'jnp' | 'pallas' (fused TPU kernels; interpret on CPU)

    def indices(self, p: int) -> np.ndarray:
        return make_index_set(self.index_set, self.n, p, self.degree)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("eps", "rho", "noise", "omega"),
    meta_fields=("n", "index_set", "degree", "block_rows", "store_train",
                 "backend", "expansion", "approximation", "kernel",
                 "neighbors"),
)
@dataclasses.dataclass(frozen=True)
class GPSpec:
    """The one self-describing specification of a GP session.

    Merges the kernel hyperparameters and the static expansion choices so a
    session is described by exactly one object, baked into ``FAGPState`` at
    fit time.

    Pytree layout: ``eps``/``rho``/``noise``/``omega`` are data leaves —
    ``nlml`` is differentiable through them (build the loss with
    ``dataclasses.replace(spec, eps=..., ...)``); everything else is static
    metadata and participates in jit cache keys.

    eps:    per-dimension inverse length scales, shape (p,). Paper's eps_j.
    rho:    per-dimension global scale factors, shape (p,). Paper's rho_j
            (Mercer Gaussian-measure scale; unused by the RFF families).
    noise:  observation noise std sigma_n (scalar).
    omega:  (R, p) eps-free spectral base draws for the RFF expansions
            (None for ``hermite``); drawn once at spec creation and frozen
            into the factorization like any other hyperparameter.
    expansion: registered :class:`~repro.core.expansions.KernelExpansion`
            name ('hermite' | 'rff_se' | 'rff_matern52' | plugins).
    n:      eigenvalues per input dimension (paper's n; hermite only).
    index_set / degree: multi-index truncation (hermite only; see
            ``mercer.make_index_set``).
    block_rows: row-block size for the streaming moment accumulation.
    store_train: keep (Phi, y) in the fitted state (needed for mode='paper').
    backend: execution backend name in the registry ('jnp' | 'pallas').
    approximation: registered approximation family behind the GP facade
            ('fagp' — this module, the paper's decomposed-kernel technique
            — or 'vecchia'; see ``core.approximation``).  The default keeps
            every pre-protocol spec, checkpoint and call site bit-exact.
    kernel / neighbors: the Vecchia family's structure (exact reference
            kernel name 'se' | 'matern52', conditioning-set size k); must
            stay None on 'fagp' specs, whose structure is the expansion.
    """

    eps: jax.Array
    rho: jax.Array
    noise: jax.Array
    n: int
    index_set: IndexSetKind = "full"
    degree: Optional[int] = None
    block_rows: int = 4096
    store_train: bool = False
    backend: str = "jnp"
    expansion: str = "hermite"
    omega: Optional[jax.Array] = None
    approximation: str = "fagp"
    kernel: Optional[str] = None
    neighbors: Optional[int] = None

    @staticmethod
    def create(
        n: int,
        eps,
        rho=2.0,
        noise=1e-2,
        *,
        index_set: IndexSetKind = "full",
        degree: Optional[int] = None,
        block_rows: int = 4096,
        store_train: bool = False,
        backend: str = "jnp",
        expansion: str = "hermite",
        num_features: Optional[int] = None,
        seed: int = 0,
        omega=None,
        approximation: str = "fagp",
        kernel: Optional[str] = None,
        neighbors: Optional[int] = None,
    ) -> "GPSpec":
        """Convenience constructor with scalar broadcasting: ``eps`` fixes
        p, scalars broadcast.  For non-deterministic expansions (the RFF
        families) the spectral base draws are drawn here from
        ``(num_features, seed)`` — or pass ``omega`` explicitly — and ride
        on the spec as a data leaf.  The spec is validated by its
        approximation family HERE (an unknown ``approximation`` name or a
        family-invalid field combination raises at construction, never at
        fit time)."""
        eps = jnp.atleast_1d(jnp.asarray(eps, jnp.float32))
        rho = jnp.broadcast_to(jnp.asarray(rho, jnp.float32), eps.shape)
        if omega is None:
            if num_features is not None and num_features < 1:
                raise ValueError(
                    f"num_features must be >= 1, got {num_features}"
                )
            omega = get_expansion(expansion).draw_spec_data(
                eps.shape[0], 256 if num_features is None else num_features,
                seed,
            )
            if omega is None and num_features is not None:
                # a deterministic expansion silently ignoring num_features
                # almost always means a forgotten expansion= argument
                raise ValueError(
                    f"expansion {expansion!r} draws no spectral data; "
                    f"num_features only applies to the RFF families — did "
                    f"you mean expansion='rff_se' / 'rff_matern52'?"
                )
        elif get_expansion(expansion).draw_spec_data(1, 1, 0) is None:
            raise ValueError(
                f"expansion {expansion!r} takes no omega (it draws no "
                f"spectral data)"
            )
        elif num_features is not None and np.shape(omega)[0] != num_features:
            raise ValueError(
                f"explicit omega has {np.shape(omega)[0]} rows but "
                f"num_features={num_features}"
            )
        spec = GPSpec(
            eps=eps, rho=rho, noise=jnp.asarray(noise, jnp.float32),
            n=int(n), index_set=index_set, degree=degree,
            block_rows=block_rows, store_train=store_train, backend=backend,
            expansion=expansion,
            omega=None if omega is None else jnp.asarray(omega, jnp.float32),
            approximation=approximation, kernel=kernel,
            neighbors=None if neighbors is None else int(neighbors),
        )
        get_approximation(approximation).validate(spec)
        return spec

    @staticmethod
    def create_rff(
        eps,
        noise=1e-2,
        *,
        kernel: str = "se",
        num_features: int = 256,
        seed: int = 0,
        rho=2.0,
        block_rows: int = 4096,
        store_train: bool = False,
        backend: str = "jnp",
    ) -> "GPSpec":
        """Sugar for the RFF families: ``kernel`` is 'se' or 'matern52',
        ``num_features`` is the number R of spectral frequencies (the
        feature count is M = 2R; Monte-Carlo error O(1/sqrt(R)))."""
        return GPSpec.create(
            1, eps, rho, noise, block_rows=block_rows,
            store_train=store_train, backend=backend,
            expansion=f"rff_{kernel}", num_features=num_features, seed=seed,
        )

    @staticmethod
    def create_vecchia(
        eps,
        noise=1e-2,
        *,
        kernel: str = "se",
        neighbors: int = 32,
        rho=2.0,
        block_rows: int = 4096,
        backend: str = "jnp",
    ) -> "GPSpec":
        """Sugar for the Vecchia nearest-neighbor family
        (``core.vecchia``): ``kernel`` names the exact reference oracle
        ('se' | 'matern52'), ``neighbors`` is the conditioning-set size k.
        The expansion fields are inert for this family."""
        return GPSpec.create(
            1, eps, rho, noise, block_rows=block_rows, backend=backend,
            approximation="vecchia", kernel=kernel, neighbors=neighbors,
        )

    @staticmethod
    def from_parts(params: SEKernelParams, cfg: FAGPConfig) -> "GPSpec":
        """Merge a legacy (params, cfg) pair into one (hermite) spec."""
        return GPSpec(
            eps=params.eps, rho=params.rho, noise=params.noise,
            n=cfg.n, index_set=cfg.index_set, degree=cfg.degree,
            block_rows=cfg.block_rows, store_train=cfg.store_train,
            backend=cfg.backend,
        )

    @property
    def p(self) -> int:
        return self.eps.shape[0]

    @property
    def params(self) -> SEKernelParams:
        return SEKernelParams(eps=self.eps, rho=self.rho, noise=self.noise)

    @property
    def cfg(self) -> FAGPConfig:
        return FAGPConfig(
            n=self.n, index_set=self.index_set, degree=self.degree,
            block_rows=self.block_rows, store_train=self.store_train,
            backend=self.backend,
        )

    def indices(self, p: Optional[int] = None) -> np.ndarray:
        """The expansion's static (M, w) index table — its row count is M."""
        return get_expansion(self.expansion).indices(self, p or self.p)

    def n_features(self, p: Optional[int] = None) -> int:
        return self.indices(p).shape[0]

    def replace(self, **overrides) -> "GPSpec":
        return dataclasses.replace(self, **overrides)

    def describe(self) -> str:
        """Short human-readable summary for error messages."""
        if self.approximation != "fagp":
            return (
                f"GPSpec(approximation={self.approximation!r}, "
                f"kernel={self.kernel!r}, neighbors={self.neighbors}, "
                f"p={self.p}, backend={self.backend!r})"
            )
        extra = (
            f"n={self.n}, index_set={self.index_set!r}, degree={self.degree}"
            if self.expansion == "hermite"
            else f"R={0 if self.omega is None else np.shape(self.omega)[0]}"
        )
        return (
            f"GPSpec(expansion={self.expansion!r}, {extra}, p={self.p}, "
            f"backend={self.backend!r}, store_train={self.store_train})"
        )


# spec fields frozen into the factorization: with_spec calls may not change
# these on a fitted state (idx, lam, chol all depend on them; for vecchia
# the kernel/neighbor structure likewise defines the session)
_STRUCTURAL_FIELDS = ("approximation", "expansion", "n", "index_set",
                      "degree", "kernel", "neighbors")
_HYPER_FIELDS = ("eps", "rho", "noise", "omega")


def _leaf_equal(a, b) -> bool:
    if a is None or b is None:
        return a is None and b is None
    a, b = np.asarray(a), np.asarray(b)
    # All device math is f32: a python-float leaf (f64 on the host, e.g.
    # noise=0.1) and its f32 device/checkpoint round-trip are the same
    # hyperparameter, so compare in the compute dtype.
    if a.dtype == np.float64:
        a = a.astype(np.float32)
    if b.dtype == np.float64:
        b = b.astype(np.float32)
    return a.shape == b.shape and np.array_equal(a, b)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FAGPState:
    """Fitted FAGP sufficient statistics (scaled-system form).

    Self-describing: ``spec`` carries everything a consumer needs to derive
    features, backend and block sizes — no call site re-passes configuration.
    """

    idx: jax.Array            # (M, w) expansion index table (static content)
    lam: jax.Array            # (M,)   expansion weights (may underflow; info only)
    sqrtlam: jax.Array        # (M,)   exp(0.5 log lambda) — the scaling D
    chol: jax.Array           # (M, M) lower Cholesky of B = I + D G D / sigma^2
    u: jax.Array              # (M,) or (M, T) mean weights Lbar^{-1} Phi^T y / sigma^2
    params: SEKernelParams
    Phi: Optional[jax.Array]  # (N, M) train features   (store_train only)
    y: Optional[jax.Array]    # (N,) or (N, T) train targets (store_train only)
    b: Optional[jax.Array] = None    # (M,) / (M, T) raw moment Phi^T y — fit_update
    spec: Optional[GPSpec] = None    # baked at fit time; None only on internal states

    @property
    def n_features(self) -> int:
        return self.idx.shape[0]

    @property
    def n_tasks(self) -> int:
        return 1 if self.u.ndim == 1 else self.u.shape[1]

    def with_spec(self, spec: Optional[GPSpec] = None, **overrides) -> "FAGPState":
        """Escape hatch: swap execution knobs (backend, block_rows) at serve
        time, or attach a spec to an internal spec-less state.

        Validates that the requested spec regenerates *exactly* the index
        table and hyperparameters this state was factorized with —
        structural changes (expansion, n, index_set, degree, eps, rho,
        noise, omega) are rejected because chol/u/lam are frozen functions
        of them.
        """
        if spec is None:
            if self.spec is None:
                raise ValueError(
                    "state has no baked spec to override; pass a full GPSpec: "
                    "state.with_spec(spec)"
                )
            spec = dataclasses.replace(self.spec, **overrides)
        elif overrides:
            raise TypeError("pass either a full spec or keyword overrides, not both")

        if self.spec is not None:
            for f in _STRUCTURAL_FIELDS:
                if getattr(spec, f) != getattr(self.spec, f):
                    raise ValueError(
                        f"spec/state mismatch: state was fitted with "
                        f"{self.spec.describe()} but the new spec has "
                        f"{f}={getattr(spec, f)!r}; structural choices are "
                        f"frozen into the factorization — refit instead"
                    )
        _check_spec_regenerates_idx(self, spec)
        _check_hypers_match(self, spec, "with_spec")
        if spec.store_train and self.Phi is None:
            raise ValueError(
                "with_spec cannot enable store_train on an already-fitted state "
                "(the training features were never stored); refit with "
                "store_train=True"
            )
        _check_backend_support(spec)
        return dataclasses.replace(self, spec=spec, params=spec.params)


def _check_hypers_match(state: "FAGPState", spec: "GPSpec", who: str) -> None:
    """Raise unless ``spec`` carries exactly the hyperparameter leaves
    (eps/rho/noise, plus any RFF spectral draws) the state was factorized
    with — the data half of every spec/state compatibility check (shared by
    ``FAGPState.with_spec`` and the bank's membership validation)."""
    for f in _HYPER_FIELDS:
        # spec-less states carry no omega record, so they compare as None:
        # a spec WITH spectral draws can never attach to one (we could not
        # verify the draws match the factorization), which also blocks the
        # cross-family aliasing where an RFF arange(2R) index table happens
        # to equal a 1-D hermite grid
        have = (
            getattr(state.spec, f) if state.spec is not None
            else getattr(state.params, f, None)
        )
        if not _leaf_equal(getattr(spec, f), have):
            raise ValueError(
                f"{who}: spec/state mismatch: {f} differs from the value "
                f"this state was fitted with; hyperparameters are frozen "
                f"into the factorization — refit (or fit_update) instead"
            )


def _check_spec_regenerates_idx(state: "FAGPState", spec: "GPSpec") -> None:
    """Raise unless ``spec`` regenerates exactly the index table baked into
    the state — the structural half of every spec/state compatibility
    check."""
    idx_np = np.asarray(state.idx)
    want = spec.indices()
    if want.shape != idx_np.shape or not np.array_equal(want, idx_np):
        fitted = state.spec.describe() if state.spec is not None else (
            f"an index table of shape {idx_np.shape}"
        )
        raise ValueError(
            f"spec/state mismatch: this state was fitted with {fitted}, but "
            f"{spec.describe()} generates a different index table; the "
            f"expansion structure is frozen into the factorization — refit "
            f"instead"
        )


def build_features(X: jax.Array, spec: GPSpec,
                   idx: Optional[jax.Array] = None) -> jax.Array:
    """Phi_(X) under the spec's expansion (jnp reference path).
    (N, p) -> (N, M).  ``idx`` defaults to the spec's own index table."""
    if idx is None:
        idx = jnp.asarray(spec.indices())
    return get_expansion(spec.expansion).features(X, idx, spec)


def _features(X: jax.Array, idx: jax.Array, spec: GPSpec) -> jax.Array:
    return get_expansion(spec.expansion).features(X, idx, spec)


def _tscale(d: jax.Array, v: jax.Array) -> jax.Array:
    """Scale the leading (M) axis of v by d, for v of shape (M,) or (M, T)."""
    return d[:, None] * v if v.ndim == 2 else d * v


def _row_weight(mi: jax.Array, v: jax.Array) -> jax.Array:
    """Apply a per-row mask/weight mi (N,) to v of shape (N,) or (N, T)."""
    return mi[:, None] * v if v.ndim == 2 else mi * v


def _assemble_scaled_system(G: jax.Array, loglam: jax.Array, sig2) -> tuple:
    """The single home of the f32 log-space scaled system (shared by fit,
    nlml and the distributed schedules):

        B = I + D G D / sigma^2,      D = diag(exp(0.5 log lambda))

    Returns (B, sqrtlam).  Assembling from log eigenvalues keeps columns
    whose lambda underflows f32 as inert identity rows instead of NaNs.
    """
    M = G.shape[0]
    sqrtlam = jnp.exp(0.5 * loglam)
    B = jnp.eye(M, dtype=G.dtype) + (sqrtlam[:, None] * G * sqrtlam[None, :]) / sig2
    return B, sqrtlam


def _solve_mean_weights(chol, sqrtlam, b, sig2):
    """u = Lbar^{-1} b / sig2 = D B^{-1} D b / sig2, batched over task
    columns when b is (M, T) — the T tasks share the one Cholesky factor."""
    return _tscale(
        sqrtlam, jax.scipy.linalg.cho_solve((chol, True), _tscale(sqrtlam, b))
    ) / sig2


def _block_scan_moments(X, y, feats_fn, M: int, block_rows: int,
                        row_mask=None, want_gram: bool = True):
    """The one home of the streaming row-block scaffolding (pad, reshape,
    mask, scan): G = Phi^T Phi and b = Phi^T y accumulated block by block,
    O(M^2) live memory.  ``feats_fn(Xi) -> (block, M)`` supplies the feature
    tiles (jnp reference or a Pallas kernel); ``want_gram=False`` skips the
    Gram GEMM when only b is needed.  y may be (N,) or (N, T)."""
    N = X.shape[0]
    nblk = max(1, (N + block_rows - 1) // block_rows)
    pad = nblk * block_rows - N
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    yp = jnp.pad(y, ((0, pad),) + ((0, 0),) * (y.ndim - 1))
    valid = jnp.ones((N,), X.dtype) if row_mask is None else row_mask.astype(X.dtype)
    mask = jnp.pad(valid, (0, pad))

    Xb = Xp.reshape(nblk, block_rows, -1)
    yb = yp.reshape((nblk, block_rows) + y.shape[1:])
    mb = mask.reshape(nblk, block_rows)

    def step(carry, blk):
        G, b = carry
        Xi, yi, mi = blk
        Phi_i = feats_fn(Xi) * mi[:, None]
        if want_gram:
            G = G + jnp.matmul(Phi_i.T, Phi_i, precision=_HIGHEST)
        b = b + jnp.matmul(Phi_i.T, _row_weight(mi, yi), precision=_HIGHEST)
        return (G, b), None

    init = (jnp.zeros((M, M), X.dtype), jnp.zeros((M,) + y.shape[1:], X.dtype))
    (G, b), _ = jax.lax.scan(step, init, (Xb, yb, mb))
    return G, b


def _accumulate_moments(X, y, spec, idx, block_rows: int, row_mask=None):
    """Streaming G = Phi^T Phi, b = Phi^T y over row blocks (O(M^2) memory),
    under the spec's expansion.

    y may be (N,) or multi-output (N, T); b comes back (M,) or (M, T)."""
    return _block_scan_moments(
        X, y, lambda Xi: _features(Xi, idx, spec),
        idx.shape[0], block_rows, row_mask=row_mask,
    )


@jax.jit
def _factor(B, b, sqrtlam, sig2):
    """The fit's M x M solve: Cholesky of B and the mean weights.  Jitted
    on arrays alone, so every backend's fit shares this one program — at
    paper scale (M = 14641) it is most of the fit's compile time."""
    chol = jnp.linalg.cholesky(B)
    return chol, _solve_mean_weights(chol, sqrtlam, b, sig2)


def _finish_fit(B, b, loglam, sqrtlam, sig2, idx, params, Phi, y):
    """Shared fit epilogue: M x M Cholesky solve -> FAGPState."""
    chol, u = _factor(B, b, sqrtlam, sig2)
    return FAGPState(
        idx=idx, lam=jnp.exp(loglam), sqrtlam=sqrtlam, chol=chol, u=u,
        params=params, Phi=Phi, y=y, b=b,
    )


@jax.jit
def _jnp_system(X, y, spec: GPSpec, idx):
    """jnp-backend scaled system (B, b, log lambda, sqrt lambda): the
    spec's static metadata keys the jit cache, its data leaves
    (eps/rho/noise/omega) are traced."""
    loglam = get_expansion(spec.expansion).log_eigenvalues(idx, spec)
    G, b = _accumulate_moments(X, y, spec, idx, spec.block_rows)
    B, sqrtlam = _assemble_scaled_system(G, loglam, spec.noise**2)
    return B, b, loglam, sqrtlam


def _fit(X, y, spec: GPSpec, idx):
    """jnp-backend fit: streamed moments, then the shared solve."""
    B, b, loglam, sqrtlam = _jnp_system(X, y, spec, idx)
    Phi = _features_jit(X, spec, idx) if spec.store_train else None
    return _finish_fit(B, b, loglam, sqrtlam, spec.noise**2, idx,
                       spec.params, Phi, y if spec.store_train else None)


def _pallas_streamed_bt(X, Y, consts, table, spec, tile):
    """Per-task moment vectors b = Phi^T Y for multi-output fits on the
    Pallas backend: feature tiles come from the expansion kernel one row
    block at a time, so only a (block_rows, M) tile is ever live."""
    from repro.kernels import ops as kops

    _, b = _block_scan_moments(
        X, Y,
        lambda Xi: kops.expansion_phi(Xi, consts, table, n_max=spec.n,
                                      tile_fn=tile),
        table.shape[1], spec.block_rows, want_gram=False,
    )
    return b


@jax.jit
def _pallas_system(X, y, spec: GPSpec, idx, aux):
    """The scaled system on the streaming fused Pallas kernel: feature
    tiles are generated on the fly inside the Gram accumulation
    (kernels/phi_gram) by the expansion's tile builder, so Phi never exists
    in HBM and peak live memory is O(M^2) in N — one HBM pass over X
    instead of the materialized path's two passes plus an N x M
    intermediate.

    Multi-output y (N, T): the shared scaled Gram B comes from the fused
    kernel exactly as in the single-output case; the per-task moment vectors
    are streamed block-wise through the expansion feature kernel.  Known
    cost: this is a SECOND pass over X that regenerates the feature tiles
    (still O(M T) live memory, never an N x M buffer) — teaching phi_gram
    to accumulate (M, T) moments in its one pass is the planned follow-up."""
    from repro.kernels import ops as kops

    exp = get_expansion(spec.expansion)
    loglam = exp.log_eigenvalues(idx, spec)
    sqrtlam = jnp.exp(0.5 * loglam)
    consts = exp.tile_consts(spec)
    table = exp.tile_table(aux, spec)
    tile = exp.tile_fn()
    y0 = y if y.ndim == 1 else y[:, 0]
    B, b = kops.fused_fit_moments(X, y0, consts, table, sqrtlam,
                                  spec.noise**2, n_max=spec.n, tile_fn=tile)
    if y.ndim == 2:
        b = _pallas_streamed_bt(X, y, consts, table, spec, tile)
    return B, b, loglam, sqrtlam


def _fit_pallas(X, y, spec: GPSpec, idx, aux):
    """fit() on the Pallas backend: the fused-kernel system, then the
    shared solve.  (store_train=True additionally materializes Phi for
    mode='paper' prediction, reintroducing the N x M buffer by request.)"""
    B, b, loglam, sqrtlam = _pallas_system(X, y, spec, idx, aux)
    Phi = _pallas_features(X, spec, idx, aux) if spec.store_train else None
    return _finish_fit(B, b, loglam, sqrtlam, spec.noise**2, idx,
                       spec.params, Phi, y if spec.store_train else None)


# ---------------------------------------------------------------------------
# Backend registry — capability-declaring plugins, one dispatch point shared
# by fit / predict_mean_var / core.distributed (per-shard moments).  A new
# execution backend plugs in by registering one FitBackend; ``supports``
# lets it refuse specs it cannot run with a clear error at the call boundary
# instead of crashing deep inside ``prepare`` or a kernel launch.
# ---------------------------------------------------------------------------


def _supports_everything(spec: "GPSpec") -> Optional[str]:
    return None


@dataclasses.dataclass(frozen=True)
class FitBackend:
    """Execution backend for the FAGP hot paths.  Every hook receives the
    session's ``GPSpec`` and resolves the feature map through the expansion
    registry — backends execute, expansions define the math.

    prepare:  (idx_np, spec) -> static auxiliary carried to every call
              (e.g. the Hermite one-hot selection for the Pallas kernels);
              None if unused.
    fit:      (X, y, idx, aux, spec) -> FAGPState (spec baked by the caller).
    features: (X, spec, idx, aux) -> (N, M) feature matrix.
    mean_var: (state, Xs, aux) -> (mu, var), the serving path.
    moments:  (X, y, spec, idx, aux, block_rows, mask) -> (G, b)
              raw sufficient statistics — the per-shard unit of work for
              core.distributed (partial sums, psum'd before the solve).
    supports: (spec) -> None if the backend can run the spec, else a short
              reason string surfaced in the ValueError raised at dispatch.

    Bank hooks (the multi-tenant fleet path, ``repro.bank.GPBank``) — both
    optional; ``bank.GPBank`` falls back to a vmap of the single-model
    entry points when a backend leaves them None:

    bank_moments:  (Xb (B,N,p), yb (B,N), spec, idx, aux,
                   block_rows, maskb (B,N)) -> (G (B,M,M), b (B,M)) — raw
                   fit moments for B independent datasets in one batched
                   call; per-slot row masks express ragged per-tenant N.
    bank_mean_var: (stack, binv (C,M,M), slots (Q,), Xq (Q,p), aux)
                   -> (mu, var) for a mixed-tenant query batch against a
                   stacked FAGPState (leading bank axis on
                   chol/u/b/lam/sqrtlam); ``binv`` is the per-slot B^{-1}
                   serving cache (``_bank_binv``), recomputed by GPBank
                   only when the stack changes.
    """

    name: str
    prepare: Callable[[np.ndarray, "GPSpec"], Any]
    fit: Callable[..., "FAGPState"]
    features: Callable[..., jax.Array]
    mean_var: Callable[..., tuple]
    moments: Callable[..., tuple]
    supports: Callable[["GPSpec"], Optional[str]] = _supports_everything
    bank_moments: Optional[Callable[..., tuple]] = None
    bank_mean_var: Optional[Callable[..., tuple]] = None


_BACKENDS: dict[str, FitBackend] = {}


def register_backend(backend: FitBackend) -> None:
    _BACKENDS[backend.name] = backend


def get_backend(name: str) -> FitBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {available_backends()}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_BACKENDS)


def _check_backend_support(spec: "GPSpec") -> FitBackend:
    """Resolve spec.expansion and spec.backend, validate the spec against
    the expansion, and enforce the backend's declared capabilities.

    Refusals are the structured :class:`UnsupportedError` shared with the
    approximation capability flags: a backend declining a spec (e.g. the
    pallas Hermite recurrence depth limit) raises with ``layer="backend"``
    and ``capability=spec.backend``; a non-FAGP spec reaching these entry
    points at all raises with ``layer="approximation"`` (route through
    ``core.gp.GP``, which dispatches by ``spec.approximation``)."""
    if spec.approximation != "fagp":
        raise UnsupportedError(
            f"the fagp module does not support {spec.describe()}: its "
            f"entry points run the 'fagp' family only — dispatch through "
            f"repro.core.gp.GP, which routes by spec.approximation",
            layer="approximation", capability="fagp", spec=spec,
        )
    get_expansion(spec.expansion).validate(spec)
    backend = get_backend(spec.backend)
    reason = backend.supports(spec)
    if reason is not None:
        raise UnsupportedError(
            f"backend {spec.backend!r} does not support {spec.describe()}: "
            f"{reason} (registered backends: {available_backends()})",
            layer="backend", capability=spec.backend, spec=spec,
        )
    return backend


# prepare() results memoized per (idx array, backend, expansion, n):
# predict_mean_var / fit_update sit on the serving hot path, and rebuilding
# the one-hot selection matrix (plus the blocking device->host idx copy) per
# microbatch is pure waste.  Keyed by id() and validated by weakref so a
# recycled id can never alias a dead array.
_AUX_CACHE: dict = {}


def _backend_aux(backend: FitBackend, idx: jax.Array, spec: "GPSpec"):
    import weakref

    key = (id(idx), backend.name, spec.expansion, spec.n)
    hit = _AUX_CACHE.get(key)
    if hit is not None and hit[0]() is idx:
        return hit[1]
    aux = backend.prepare(np.asarray(idx), spec)
    try:
        ref = weakref.ref(idx)
    except TypeError:
        return aux
    if len(_AUX_CACHE) > 64:
        _AUX_CACHE.clear()
    _AUX_CACHE[key] = (ref, aux)
    return aux


# --- jnp backend (scan-streamed, pure XLA) ---------------------------------


@jax.jit
def _features_jit(X, spec: GPSpec, idx):
    return _features(X, idx, spec)


def _jnp_features(X, spec, idx, aux):
    return _features_jit(X, spec, idx)


def _jnp_moments(X, y, spec, idx, aux, block_rows, mask=None):
    return _jnp_moments_jit(X, y, spec, idx, block_rows, mask)


@partial(jax.jit, static_argnames=("block_rows",))
def _jnp_moments_jit(X, y, spec, idx, block_rows, mask):
    return _accumulate_moments(X, y, spec, idx, block_rows, row_mask=mask)


def _jnp_fit(X, y, idx, aux, spec: "GPSpec"):
    return _fit(X, y, spec, idx)


def _jnp_mean_var(state, Xs, aux):
    return _posterior_mean_var(
        state.u, state.chol, state.sqrtlam,
        _features_jit(Xs, state.spec, state.idx),
    )


# --- bank (multi-tenant) hooks ---------------------------------------------
# One stacked FAGPState holds B independent fitted sessions (leading bank
# axis on chol/u/b/lam/sqrtlam; idx/params/spec shared).  ``bank_moments``
# computes B fits' sufficient statistics in one batched call;
# ``bank_mean_var`` answers one padded mixed-tenant query batch by gathering
# each query row's slot state — both are single compiled executables
# regardless of how many tenants are in flight (see repro.bank).


@jax.jit
def _bank_binv(chol_s):
    """Per-slot B^{-1} (C, M, M) from the stacked Cholesky factors — the
    bank's serving cache.  Computed once per bank *version* (GPBank caches
    it until the next fit/update/insert/evict), so the per-query serving
    path below is pure gather + GEMV instead of Q tiny triangular solves
    (which are dispatch-bound: one LAPACK call per query row)."""
    M = chol_s.shape[-1]
    eye = jnp.eye(M, dtype=chol_s.dtype)
    return jax.vmap(lambda c: jax.scipy.linalg.cho_solve((c, True), eye))(
        chol_s
    )


@jax.jit
def _bank_gathered_posterior(binv_s, u_s, sqrtlam_s, slots, Phis):
    """Mixed-tenant posterior from a stacked state: query row q reads slot
    ``slots[q]``.  Shared by every backend's bank_mean_var — only the
    feature construction differs.  binv_s (C,M,M) from ``_bank_binv``,
    u_s (C,M), sqrtlam_s (C,M), slots (Q,), Phis (Q,M)
    -> (mu (Q,), var (Q,))."""
    mu = jnp.sum(Phis * u_s[slots], axis=1)
    PhisD = Phis * sqrtlam_s[slots]                      # (Q, M)
    var = jnp.einsum("qm,qmn,qn->q", PhisD, binv_s[slots], PhisD,
                     precision=_HIGHEST)
    return mu, var


@partial(jax.jit, static_argnames=("block_rows",))
def _jnp_bank_moments_jit(Xb, yb, spec, idx, block_rows, maskb):
    f = lambda X, y, m: _accumulate_moments(
        X, y, spec, idx, block_rows, row_mask=m
    )
    return jax.vmap(f)(Xb, yb, maskb)


def _jnp_bank_moments(Xb, yb, spec, idx, aux, block_rows, maskb=None):
    if maskb is None:
        maskb = jnp.ones(Xb.shape[:2], Xb.dtype)
    # banks hold SMALL tenants: never let the scan pad a slot's few rows up
    # to the default serving block (the pallas path clamps block_k likewise)
    block_rows = min(block_rows, max(1, Xb.shape[1]))
    return _jnp_bank_moments_jit(Xb, yb, spec, idx, block_rows, maskb)


def _gathered_bank_mean_var(features):
    """Build a ``bank_mean_var`` from a backend's feature map: the gathered
    serving path is backend-independent (one home, above) — only the
    feature construction differs.  Used for both built-in backends and as
    the fallback for third-party backends that declare no bank hooks."""
    def f(stack, binv, slots, Xq, aux):
        Phis = features(Xq, stack.spec, stack.idx, aux)
        return _bank_gathered_posterior(
            binv, stack.u, stack.sqrtlam, slots, Phis
        )
    return f


# --- pallas backend (fused TPU kernels; interpret mode on CPU) -------------


def _pallas_supports(spec: "GPSpec") -> Optional[str]:
    # the expansion owns the tile builder, so it owns the capability answer
    # (Hermite: unrolled recurrence depth; RFF: anything goes)
    return get_expansion(spec.expansion).pallas_supports(spec)


def _pallas_prepare(idx_np: np.ndarray, spec: "GPSpec"):
    return get_expansion(spec.expansion).pallas_prepare(idx_np, spec)


def _pallas_features(X, spec, idx, aux):
    from repro.kernels import ops as kops

    exp = get_expansion(spec.expansion)
    return kops.expansion_phi(
        X, exp.tile_consts(spec), exp.tile_table(aux, spec),
        n_max=spec.n, tile_fn=exp.tile_fn(),
    )


def _pallas_moments(X, y, spec, idx, aux, block_rows, mask=None):
    from repro.kernels import ops as kops

    exp = get_expansion(spec.expansion)
    ones = jnp.ones((idx.shape[0],), jnp.float32)
    return kops.fused_fit_moments(
        X, y, exp.tile_consts(spec), exp.tile_table(aux, spec), ones,
        jnp.float32(1.0), mask, n_max=spec.n, scale=False,
        tile_fn=exp.tile_fn(),
    )


def _pallas_fit(X, y, idx, aux, spec: "GPSpec"):
    return _fit_pallas(X, y, spec, idx, aux)


def _pallas_mean_var(state, Xs, aux):
    return _posterior_mean_var(
        state.u, state.chol, state.sqrtlam,
        _pallas_features(Xs, state.spec, state.idx, aux),
    )


def _pallas_bank_moments(Xb, yb, spec, idx, aux, block_rows, maskb=None):
    """One kernel launch for the whole bank: the bank axis is a leading
    grid dimension of the streaming fused kernel, so feature tiles for
    different tenants are generated in VMEM tile-by-tile — B separate
    N x M Phis never materialize (kernels/phi_gram.bank_phi_gram_kernel),
    whichever expansion the bank's shared spec names."""
    from repro.kernels import ops as kops

    exp = get_expansion(spec.expansion)
    return kops.bank_fused_fit_moments(
        Xb, yb, exp.tile_consts(spec), exp.tile_table(aux, spec), maskb,
        n_max=spec.n, tile_fn=exp.tile_fn(),
    )


register_backend(FitBackend(
    name="jnp", prepare=lambda idx_np, spec: None, fit=_jnp_fit,
    features=_jnp_features, mean_var=_jnp_mean_var, moments=_jnp_moments,
    bank_moments=_jnp_bank_moments,
    bank_mean_var=_gathered_bank_mean_var(_jnp_features),
))
register_backend(FitBackend(
    name="pallas", prepare=_pallas_prepare, fit=_pallas_fit,
    features=_pallas_features, mean_var=_pallas_mean_var,
    moments=_pallas_moments, supports=_pallas_supports,
    bank_moments=_pallas_bank_moments,
    bank_mean_var=_gathered_bank_mean_var(_pallas_features),
))


# ---------------------------------------------------------------------------
# Public entry points — spec-first.  The split (params, cfg) signatures were
# deprecated for two releases and now raise TypeError.
# ---------------------------------------------------------------------------


def _check_p(spec: GPSpec, p: int) -> None:
    if spec.p != p:
        raise ValueError(
            f"spec/input mismatch: {spec.describe()} was built for p={spec.p} "
            f"input dimensions but the data has p={p}"
        )


def fit(X: jax.Array, y: jax.Array, spec: GPSpec, cfg: Any = None) -> FAGPState:
    """Fit the FAGP posterior; the spec is baked into the returned state.

    y: (N,) targets, or (N, T) for T tasks sharing one factorization.
    """
    if cfg is not None or not isinstance(spec, GPSpec):
        _removed(
            "fit(X, y, params, cfg)",
            "merge them with GPSpec.from_parts(params, cfg) and call "
            "fit(X, y, spec)",
        )
    _check_p(spec, X.shape[1])
    backend = _check_backend_support(spec)
    idx_np = spec.indices(X.shape[1])
    idx = jnp.asarray(idx_np)
    aux = backend.prepare(idx_np, spec)
    state = backend.fit(X, y, idx, aux, spec)
    return dataclasses.replace(state, spec=spec)


def _require_spec(state: FAGPState, call: str) -> GPSpec:
    """Derive the session spec from the state (the only source of truth now
    that the deprecated cfg re-passing was removed)."""
    if state.spec is None:
        raise ValueError(
            f"this state has no baked GPSpec (produced by an internal "
            f"path); attach one with state.with_spec(spec) before calling "
            f"{call}"
        )
    return state.spec


# ---------------------------------------------------------------------------
# Online incremental fitting (rank-k update of the scaled system)
# ---------------------------------------------------------------------------


def _chol_rank1_update(L: jax.Array, w: jax.Array) -> jax.Array:
    """Cholesky of L L^T + w w^T, O(M^2) (LINPACK positive-update sweep).

    Column-sequential Givens-style sweep expressed as a scan with masked
    whole-column updates; additions are always well-posed (no downdates)."""
    M = L.shape[0]
    ar = jnp.arange(M)

    def step(carry, k):
        L, w = carry
        Lkk = L[k, k]
        wk = w[k]
        r = jnp.sqrt(Lkk * Lkk + wk * wk)
        c = r / Lkk
        s = wk / Lkk
        col = L[:, k]
        below = ar > k
        newcol = jnp.where(below, (col + s * w) / c, col).at[k].set(r)
        w = jnp.where(below, c * w - s * newcol, w)
        return (L.at[:, k].set(newcol), w), None

    (L, _), _ = jax.lax.scan(step, (L, w), ar)
    return L


def _update_arrays(chol, b, sqrtlam, noise, Phi_new, y_new):
    """Array-level rank-K update core: (chol, b) -> (chol', b', u').

    Shared by the single-session ``fit_update`` and the bank's batched
    update (``repro.bank``, vmapped over slots — every op here batches)."""
    sig2 = noise**2
    # B_new = B + sum_k v_k v_k^T,  v_k = D phi_k / sigma  (rank-K update)
    W = Phi_new * sqrtlam[None, :] / noise
    K, M = W.shape
    if K * 8 <= M:
        # small K: sequential rank-1 sweeps, O(K M^2), beats refactorization
        chol, _ = jax.lax.scan(
            lambda L, w: (_chol_rank1_update(L, w), None), chol, W
        )
    else:
        # K comparable to M: the rank-1 sweep is K*M sequential latency-bound
        # steps; rebuilding the M x M factor is O(M^3/3) fully-parallel work
        # and still never touches the original N rows
        B = (jnp.matmul(chol, chol.T, precision=_HIGHEST)
             + jnp.matmul(W.T, W, precision=_HIGHEST))
        chol = jnp.linalg.cholesky(B)
    b = b + jnp.matmul(Phi_new.T, y_new, precision=_HIGHEST)
    u = _solve_mean_weights(chol, sqrtlam, b, sig2)
    return chol, b, u


# jitted on arrays, not on the state, so sessions that differ only in
# backend share one compiled update
_update_arrays_jit = jax.jit(_update_arrays)


def fit_update(
    state: FAGPState, X_new: jax.Array, y_new: jax.Array, cfg: Any = None,
) -> FAGPState:
    """Absorb new observations into a fitted state without refitting.

    Rank-k Cholesky update of B (O(k M^2)) plus a fresh M x M solve for the
    mean weights — no pass over the original N rows, so the serving loop can
    ingest observation microbatches at O(M^2) cost each (vs O(N M^2) refit).
    Exactly equivalent to refitting on the concatenated data (same math, up
    to f32 rounding); tests pin update-then-predict == refit-then-predict.

    Everything (expansion, backend, block size) derives from the baked spec.
    """
    if cfg is not None:
        _removed(
            "fit_update(state, X_new, y_new, cfg)",
            "the spec is baked into the state — drop the cfg",
        )
    if state.b is None:
        raise ValueError("fit_update needs a state produced by fit() >= this "
                         "version (missing the raw moment vector b)")
    if y_new.ndim != state.u.ndim or (
        y_new.ndim == 2 and y_new.shape[1] != state.u.shape[1]
    ):
        raise ValueError(
            f"fit_update task mismatch: state holds "
            f"{state.n_tasks} task(s) but y_new has shape {y_new.shape}"
        )
    spec = _require_spec(state, "fit_update(state, X_new, y_new)")
    backend = _check_backend_support(spec)
    aux = _backend_aux(backend, state.idx, spec)
    Phi_new = backend.features(X_new, spec, state.idx, aux)
    chol, b, u = _update_arrays_jit(state.chol, state.b, state.sqrtlam,
                                    state.params.noise, Phi_new, y_new)
    Phi = y = None
    if state.Phi is not None:
        Phi = jnp.concatenate([state.Phi, Phi_new], axis=0)
        y = jnp.concatenate([state.y, y_new], axis=0)
    return dataclasses.replace(state, chol=chol, b=b, u=u, Phi=Phi, y=y)


# ---------------------------------------------------------------------------
# Prediction
# ---------------------------------------------------------------------------


@jax.jit
def _predict_fused(state: FAGPState, Xs: jax.Array):
    """Beyond-paper weight-space path: no N-sized intermediates.

    Phi* Lbar^{-1} Phi*^T = (Phi* D) B^{-1} (Phi* D)^T via triangular solve.
    """
    Phis = _features(Xs, state.idx, state.spec)  # (N*, M)
    mu = jnp.matmul(Phis, state.u, precision=_HIGHEST)
    PhisD = Phis * state.sqrtlam[None, :]
    V = jax.scipy.linalg.solve_triangular(state.chol, PhisD.T, lower=True)  # (M, N*)
    cov = jnp.matmul(V.T, V, precision=_HIGHEST)
    return mu, cov


@jax.jit
def _predict_paper(state: FAGPState, Xs: jax.Array):
    """Literal Eqs. 11-12 GEMM chain in the paper's operation order.

    Requires a state fitted with store_train=True.  Forms the N x N
    approximate inverse (Sigma_n^{-1} - Sigma_n^{-1} Phi Lbar^{-1} Phi^T
    Sigma_n^{-1}) exactly as the CUDA implementation does, then W (N* x N),
    then mu*, Sigma*.
    """
    Phi, y = state.Phi, state.y
    N = Phi.shape[0]
    sig2 = state.params.noise**2
    Phis = _features(Xs, state.idx, state.spec)                 # (N*, M)
    Lam = state.lam                                             # (M,)

    D = state.sqrtlam
    LbarinvPhiT = D[:, None] * jax.scipy.linalg.cho_solve(
        (state.chol, True), D[:, None] * Phi.T
    )  # Lbar^{-1} Phi^T = D B^{-1} D Phi^T,  (M, N)
    Kinv = jnp.eye(N, dtype=Phi.dtype) / sig2 - (Phi @ LbarinvPhiT) / (sig2 * sig2)
    PhisLam = Phis * Lam[None, :]                               # Phi* Lambda
    W = (PhisLam @ Phi.T) @ Kinv                                # (N*, N) — Eq. 11's W
    mu = W @ y
    cov = PhisLam @ Phis.T - (W @ Phi) @ (Lam[:, None] * Phis.T)  # Eq. 12
    return mu, cov


def predict(state: FAGPState, Xs: jax.Array, cfg: Any = None,
            mode: str = "fused"):
    """Posterior mean and covariance (N*, N*) at Xs.

    Mean is (N*,) or (N*, T) for multi-output states; the covariance is
    shared across tasks (one kernel, one noise level).  Everything derives
    from the spec baked into the state.
    """
    if cfg is not None:
        _removed(
            "predict(state, Xs, cfg)",
            "the spec is baked into the state — drop the cfg",
        )
    spec = _require_spec(state, "predict(state, Xs)")
    if mode == "fused":
        return _predict_fused(state, Xs)
    if mode == "paper":
        if state.Phi is None:
            raise ValueError(
                f"mode='paper' needs the training features stored in the "
                f"fitted state, but this state was fitted with "
                f"{spec.replace(store_train=False).describe()} — refit with a "
                f"spec that sets store_train=True"
            )
        return _predict_paper(state, Xs)
    raise ValueError(f"unknown mode {mode!r}")


@jax.jit
def _posterior_mean_var(u, chol, sqrtlam, Phis):
    """Posterior mean and marginal variance from query features Phis
    (N*, M) — the one home of the single-model serving math, shared by
    every backend's ``mean_var`` (only the feature construction differs,
    as in the bank's ``_bank_gathered_posterior``):

        var = diag(Phis D B^{-1} D Phis^T) = colsum(V * V),
        V = L^{-1} (Phis D)^T, L = chol(B)  — one triangular solve with
        N* columns.

    Never forms B^{-1}: at M = 14641 the M x M inverse is ~M^3 work per
    call, and the TPU compiler needs more HBM than a v5e holds for it."""
    mu = jnp.matmul(Phis, u, precision=_HIGHEST)
    PhisD = Phis * sqrtlam[None, :]
    V = jax.scipy.linalg.solve_triangular(chol, PhisD.T, lower=True)
    return mu, jnp.sum(V * V, axis=0)


def predict_mean_var(state: FAGPState, Xs: jax.Array, cfg: Any = None):
    """Posterior mean and *marginal variance* (N*,) — the production serving
    path: never materializes the N* x N* covariance.

    Mean is (N*,) or (N*, T) for multi-output states; the variance is shared
    across tasks.  Expansion, backend and n_max derive from the baked spec."""
    if cfg is not None:
        _removed(
            "predict_mean_var(state, Xs, cfg)",
            "the spec is baked into the state — drop the cfg",
        )
    spec = _require_spec(state, "predict_mean_var(state, Xs)")
    backend = _check_backend_support(spec)
    aux = _backend_aux(backend, state.idx, spec)
    return backend.mean_var(state, Xs, aux)


# ---------------------------------------------------------------------------
# Negative log marginal likelihood (paper's declared future work)
#
# The NLML path runs through the backend registry's ``moments`` hooks — the
# same per-shard unit of work core.distributed sums — so evaluating (and
# optimizing) the marginal likelihood never materializes the N x M feature
# matrix on EITHER backend: the pallas hook streams tiles through the fused
# kernel, the jnp hook scans row blocks.  The hooks themselves are not
# differentiable (the pallas kernel has no AD rule), so the moments are
# wrapped in a custom VJP whose backward pass is the streamed jnp block
# scan differentiated through the expansion's feature map — also O(M^2)
# live memory (pinned by the jaxpr sweep in tests/test_gp_hyperopt.py).
# ---------------------------------------------------------------------------


def _moments_via_registry(spec: GPSpec, X, y, mask):
    """Raw (G, b) = (Phi^T Phi, Phi^T y) over the masked rows, dispatched
    through ``spec.backend``'s moments hook (value path; see
    ``_moments_diff`` for the differentiable wrapper)."""
    backend = get_backend(spec.backend)
    idx_np = spec.indices(X.shape[1])
    aux = backend.prepare(idx_np, spec)
    # never let the scan pad a small problem's rows up to the serving block
    block_rows = min(spec.block_rows, max(1, X.shape[0]))
    return backend.moments(X, y, spec, jnp.asarray(idx_np), aux,
                           block_rows, mask)


@jax.custom_vjp
def _moments_diff(spec: GPSpec, X, y, mask):
    return _moments_via_registry(spec, X, y, mask)


def _moments_diff_fwd(spec, X, y, mask):
    return _moments_via_registry(spec, X, y, mask), (spec, X, y, mask)


def _moments_diff_bwd(res, ct):
    """Streamed VJP into EVERY primal input — the spec's data leaves
    (eps/rho/noise/omega) AND the data (X, y, mask): the cotangent
    contraction <Gbar, Phi^T Phi> + <bbar, Phi^T y> is re-derived
    block-by-block through the jnp feature map, so the backward pass holds
    one (block_rows, M) tile at a time — never an N x M buffer.  Data
    cotangents matter to callers differentiating the NLML through the
    observations (input selection, sensitivity analysis) — dropping them
    would silently corrupt those gradients."""
    spec, X, y, mask = res
    Gbar, bbar = ct
    idx = jnp.asarray(spec.indices(X.shape[1]))
    block_rows = min(spec.block_rows, max(1, X.shape[0]))

    def contracted(spec_d, X_d, y_d, mask_d):
        G, b = _block_scan_moments(
            X_d, y_d, lambda Xi: _features(Xi, idx, spec_d),
            idx.shape[0], block_rows, row_mask=mask_d,
        )
        return jnp.sum(Gbar * G) + jnp.sum(bbar * b)

    return jax.grad(contracted, argnums=(0, 1, 2, 3))(spec, X, y, mask)


_moments_diff.defvjp(_moments_diff_fwd, _moments_diff_bwd)


def _nlml_core(X, y, spec: GPSpec, mask):
    """Traceable masked NLML: moments via the backend registry
    (differentiable through ``_moments_diff``), epilogue through the shared
    scaled system.  ``mask`` (N,) of 0/1 row weights makes padding rows
    mathematically invisible (N in the logdet/normalization terms is the
    mask sum) — the unit the (B tenants x R restarts) hyperparameter
    optimizer vmaps over (repro.optim.gp_hyperopt)."""
    exp = get_expansion(spec.expansion)
    idx = jnp.asarray(spec.indices(X.shape[1]))
    T = 1 if y.ndim == 1 else y.shape[1]
    sig2 = spec.noise**2
    loglam = exp.log_eigenvalues(idx, spec)
    G, b = _moments_diff(spec, X, y, mask)
    n_eff = jnp.sum(mask)
    B, sqrtlam = _assemble_scaled_system(G, loglam, sig2)
    chol = jnp.linalg.cholesky(B)
    bs = _tscale(sqrtlam, b) / sig2              # D b / sig2, per task column
    w = jax.scipy.linalg.cho_solve((chol, True), bs)
    # y^T Kinv y = y^T y/sig2 - b^T Lbar^{-1} b / sig2^2
    #            = y^T y/sig2 - (Db/sig2)^T B^{-1} (Db/sig2), summed over tasks
    quad = jnp.sum(_row_weight(mask, y) * y) / sig2 - jnp.sum(bs * w)
    # logdet(K) = logdet(B) + N log sig2   (determinant lemma, scaled form);
    # the T tasks share K, so the logdet terms appear once per task
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol))) + n_eff * jnp.log(sig2)
    return 0.5 * (quad + T * (logdet + n_eff * jnp.log(2.0 * jnp.pi)))


@jax.jit
def _nlml_jit(X, y, spec: GPSpec, mask):
    return _nlml_core(X, y, spec, mask)


def nlml(X, y, spec: GPSpec, idx=None, n_max: Optional[int] = None,
         block_rows: Optional[int] = None, *, mask=None):
    """NLML of the decomposed-kernel GP, O(N M^2 + M^3).

    Matrix determinant lemma + Woodbury on (Phi Lambda Phi^T + sigma^2 I),
    assembled through the same scaled system as ``fit``, with the moment
    accumulation dispatched through the spec's backend (registry moments
    hook — streamed on both backends).  Differentiable in the spec's (eps,
    rho, noise) leaves for gradient-based hyperparameter learning — for the
    RFF expansions the lengthscale gradient flows through the eps-scaled
    spectral frequencies (``GP.optimize``, examples/hyperparam_learning.py).
    For multi-output y (N, T) the tasks share one factorization and the
    result is the sum of the per-task NLMLs.

    mask: optional (N,) row validity — masked-out rows contribute nothing
    (the batched fleet optimizer expresses ragged per-tenant N this way).
    """
    if idx is not None or n_max is not None or not isinstance(spec, GPSpec):
        _removed(
            "nlml(X, y, params, idx, n_max)",
            "build a GPSpec and call nlml(X, y, spec)",
        )
    _check_p(spec, X.shape[1])
    _check_backend_support(spec)
    if block_rows is not None:
        spec = spec.replace(block_rows=block_rows)
    if mask is None:
        mask = jnp.ones((X.shape[0],), jnp.float32)
    else:
        mask = jnp.asarray(mask).astype(jnp.float32)
        if mask.shape != (X.shape[0],):
            raise ValueError(
                f"nlml mask must be (N,) = ({X.shape[0]},), got {mask.shape}"
            )
    return _nlml_jit(X, y, spec, mask)


# ---------------------------------------------------------------------------
# The registered approximation family — FAGP as one plugin behind the GP
# facade (core.approximation).  Everything above stays the module-level
# expert API; the protocol adapter below is what ``GP`` dispatches through,
# and what makes Vecchia (core.vecchia) a true sibling rather than a fork.
# ---------------------------------------------------------------------------


_CKPT_LEAVES = ("lam", "sqrtlam", "chol", "u", "b")


class _FagpApproximation(Approximation):
    """``spec.approximation == "fagp"``: the paper's decomposed-kernel
    family.  Full capability surface, including bank admission."""

    name = "fagp"
    capabilities = frozenset(
        {"fit", "predict", "mean_var", "update", "nlml", "optimize", "bank"}
    )
    state_type = FAGPState

    def validate(self, spec: "GPSpec") -> None:
        if spec.kernel is not None or spec.neighbors is not None:
            raise ValueError(
                f"kernel=/neighbors= are vecchia-only spec fields but "
                f"approximation='fagp'; the FAGP family's structure is its "
                f"expansion — use GPSpec.create_vecchia for the Vecchia "
                f"family ({spec.describe()})"
            )
        get_expansion(spec.expansion).validate(spec)

    def fit(self, X, y, spec):
        return fit(X, y, spec)

    def predict(self, state, Xs, *, mode: str = "fused"):
        return predict(state, Xs, mode=mode)

    def mean_var(self, state, Xs):
        return predict_mean_var(state, Xs)

    def update(self, state, X_new, y_new):
        return fit_update(state, X_new, y_new)

    def nlml(self, X, y, spec, *, mask=None):
        return nlml(X, y, spec, mask=mask)

    def optimize(self, X, y, spec, *, steps: int = 100, lr: float = 5e-2,
                 restarts: int = 1, tol: Optional[float] = None,
                 jitter: float = 0.3, seed: int = 0, callback=None):
        """Gradient NLML hyperparameter learning on the fleet lane engine
        (``repro.optim.gp_hyperopt``), then a fit at the learned
        hyperparameters — the body behind ``GP.optimize``."""
        from repro.optim import gp_hyperopt

        def cb(step, vals, hp):
            if callback is None:
                return
            r = int(np.argmin(vals[0]))
            lane = {f: leaf[0, r] for f, leaf in hp.items()}
            callback(
                step, float(vals[0, r]),
                dataclasses.replace(
                    spec,
                    eps=jnp.exp(lane["log_eps"]),
                    rho=jnp.exp(lane["log_rho"]),
                    noise=jnp.exp(lane["log_noise"]),
                ),
            )

        result = gp_hyperopt.optimize_restarts(
            X, y, spec, restarts=restarts, steps=steps, lr=lr, tol=tol,
            jitter=jitter, seed=seed, callback=cb,
        )
        return fit(X, y, result.spec_for(spec, 0))

    # -- checkpoint hooks (repro.checkpoint.gpstate) ------------------------

    def ckpt_leaf_names(self) -> tuple:
        return _CKPT_LEAVES

    def ckpt_leaves(self, state: FAGPState) -> dict:
        if state.b is None:
            raise ValueError(
                "save_state: state lacks the raw moment vector b (a "
                "pre-PR-1 fit path); refit before saving"
            )
        return {f: getattr(state, f) for f in _CKPT_LEAVES}

    def ckpt_meta(self, state: FAGPState) -> dict:
        return {"M": int(state.n_features), "n_tasks": int(state.n_tasks)}

    def ckpt_rebuild(self, spec, leaves: dict, train) -> FAGPState:
        train = train or {}
        return FAGPState(
            idx=jnp.asarray(spec.indices()),
            lam=leaves["lam"], sqrtlam=leaves["sqrtlam"],
            chol=leaves["chol"], u=leaves["u"], params=spec.params,
            Phi=train.get("Phi"), y=train.get("y"), b=leaves["b"],
            spec=spec,
        )


register_approximation(_FagpApproximation())

# importing the sibling family registers it; must come AFTER this module's
# definitions (vecchia pulls _STRUCTURAL_FIELDS etc. lazily, never at its
# module scope — see the layering note in core/vecchia.py)
from . import vecchia as _vecchia  # noqa: E402,F401  (registration import)
