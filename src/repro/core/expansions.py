"""KernelExpansion — the pluggable kernel-decomposition layer.

The paper's formulation is "GP with a *decomposed kernel*": everything
downstream of the feature map (the Woodbury M x M solve, the streaming
moment accumulation, the distributed schedules, the bank) only needs

    k(x, x') ~= sum_m lambda_m phi_m(x) phi_m(x')

for SOME low-rank family {(lambda_m, phi_m)}.  This module makes that
family a first-class, registered object instead of a hard-wired Hermite
eigen-expansion.  A :class:`KernelExpansion` supplies:

* static structure — ``indices(spec)`` (the (M, w) integer table baked into
  ``FAGPState.idx``; its row count IS the feature count M) and
  ``validate(spec)``;
* weights — ``log_eigenvalues(idx, spec)``, consumed by the scaled solve
  ``B = I + D G D / sigma^2`` exactly as before (log space, so families
  with geometric decay and families with flat weights share one code path);
* a jnp feature map — ``features(X, idx, spec)`` -> (N, M), differentiable
  through the spec's data leaves (NLML hyperparameter learning);
* a tile-level feature generator for the Pallas kernels — a module-level
  ``tile_fn(xt, consts, table, *, p, n_max)`` plus the ``tile_consts`` /
  ``tile_table`` arrays it consumes — usable both for standalone feature
  construction (``kernels.ops.expansion_phi``), for the fit's row chunks
  (``kernels.ops.fused_fit_moments``) and inside the bank's fused kernel
  (``kernels.phi_gram``), so every expansion fits in O(M^2) live memory;
* an exact-kernel oracle — ``exact_kernel(Xa, Xb, spec)`` — pinning
  ``Phi diag(lam) Phi^T -> k`` in the property tests.

Registered instances:

* ``hermite``      — the paper's Hermite-Mercer eigen-expansion of the SE
  kernel (Eqs. 13-20), extracted from what used to be hard-wired across
  ``GPSpec`` / ``mercer`` / the kernels; truncation error decays
  geometrically with ``spec.n``.
* ``rff_se``       — random Fourier features of the same SE kernel:
  M = 2R paired cos/sin columns over R spectral frequencies
  w_r = sqrt(2) * eps (.) omega_r with base draws omega_r ~ N(0, I)
  carried as a data leaf on the spec (``GPSpec.omega``); Monte-Carlo error
  O(1/sqrt(R)).
* ``rff_matern52`` — random Fourier features of the ARD Matern-5/2 kernel
  (lengthscale convention matched to the SE eps — see
  ``mercer.k_matern52_ard``): base draws are multivariate-t with
  2*nu = 5 degrees of freedom, omega_r = z_r * sqrt(5 / g_r), g_r ~ chi^2_5.

The lengthscale scaling sqrt(2)*eps is applied INSIDE ``features`` /
``tile_table`` (the stored ``omega`` is eps-free), so NLML gradients flow
through the RFF lengthscales exactly as they do through the Mercer
eigenvalues.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import mercer

__all__ = [
    "KernelExpansion",
    "HermiteMercerExpansion",
    "RandomFourierExpansion",
    "register_expansion",
    "get_expansion",
    "available_expansions",
]

# the Pallas Hermite kernels unroll the scaled recurrence n_max times in the
# kernel body; past this depth the unrolled program is impractical (and the
# eigenvalues have underflown f32 for ~25 columns already)
_PALLAS_MAX_N = 64


class KernelExpansion:
    """Protocol (duck-typed base) for a pluggable kernel decomposition.

    ``spec`` throughout is a :class:`repro.core.fagp.GPSpec`; expansions
    read its static metadata (n, index_set, degree, expansion) and its data
    leaves (eps, rho, noise, omega) but never import ``fagp`` (the spec is
    duck-typed to keep the layering acyclic).
    """

    name: str = "?"

    # -- static structure ---------------------------------------------------

    def validate(self, spec) -> None:
        """Raise ValueError when the spec is malformed for this expansion."""

    def indices(self, spec, p: Optional[int] = None) -> np.ndarray:
        """The (M, w) static integer table identifying the M features."""
        raise NotImplementedError

    def draw_spec_data(self, p: int, num_features: int, seed: int):
        """Random data leaves (``GPSpec.omega``) the expansion needs, or
        None for deterministic expansions."""
        return None

    # -- weights ------------------------------------------------------------

    def log_eigenvalues(self, idx: jax.Array, spec) -> jax.Array:
        """(M,) log weights lambda_m of the decomposition."""
        raise NotImplementedError

    # -- feature maps -------------------------------------------------------

    def features(self, X: jax.Array, idx: jax.Array, spec) -> jax.Array:
        """(N, M) feature matrix, pure jnp (differentiable reference path)."""
        raise NotImplementedError

    def exact_kernel(self, Xa: jax.Array, Xb: jax.Array, spec) -> jax.Array:
        """The kernel this expansion decomposes — the parity oracle."""
        raise NotImplementedError

    # -- Pallas tile contract (see kernels/hermite_phi.py) ------------------

    def pallas_supports(self, spec) -> Optional[str]:
        """None when the Pallas tile path can run this spec, else a reason
        string — surfaced by the backend registry as the structured
        :class:`~repro.core.approximation.UnsupportedError` with
        ``layer="backend"`` (e.g. the Hermite n > 64 recurrence limit)."""
        return None

    def pallas_prepare(self, idx_np: np.ndarray, spec):
        """Static auxiliary for ``tile_table`` (memoized per index set)."""
        return None

    def tile_fn(self):
        """The module-level tile builder (stable identity for jit caches)."""
        raise NotImplementedError

    def tile_consts(self, spec) -> jax.Array:
        """Small global table replicated to every tile."""
        raise NotImplementedError

    def tile_table(self, aux, spec) -> jax.Array:
        """(K, M) per-column table blocked along the feature axis."""
        raise NotImplementedError


class HermiteMercerExpansion(KernelExpansion):
    """The paper's expansion: tensor-product Hermite eigenfunctions of the
    ARD SE kernel w.r.t. a Gaussian measure (Eqs. 13-20), truncated by a
    multi-index set.  All math delegates to ``core.mercer`` — the single
    home of the eigensystem and of the scaled Hermite recurrence."""

    name = "hermite"

    def validate(self, spec) -> None:
        if spec.n < 1:
            raise ValueError(f"hermite expansion needs n >= 1, got {spec.n}")
        if spec.index_set not in ("full", "total_degree", "hyperbolic_cross"):
            raise ValueError(f"unknown index set {spec.index_set!r}")

    def indices(self, spec, p: Optional[int] = None) -> np.ndarray:
        return mercer.make_index_set(
            spec.index_set, spec.n, p or spec.p, spec.degree
        )

    def log_eigenvalues(self, idx, spec):
        return mercer.log_eigenvalues_nd(idx, spec.params)

    def features(self, X, idx, spec):
        return mercer.phi_nd(X, idx, spec.params, spec.n)

    def exact_kernel(self, Xa, Xb, spec):
        return mercer.k_se_ard(Xa, Xb, spec.eps)

    def pallas_supports(self, spec) -> Optional[str]:
        if spec.n > _PALLAS_MAX_N:
            return (
                f"n={spec.n} exceeds the unrolled Hermite recurrence depth "
                f"the kernels are built for (max {_PALLAS_MAX_N}); use "
                f"backend='jnp'"
            )
        return None

    def pallas_prepare(self, idx_np, spec):
        from repro.kernels import ref as kref

        return jnp.asarray(kref.one_hot_selection(idx_np, spec.n))

    def tile_fn(self):
        from repro.kernels.hermite_phi import phi_tile

        return phi_tile

    def tile_consts(self, spec):
        from repro.kernels import ref as kref

        return kref.phi_consts(spec.eps, spec.rho)

    def tile_table(self, aux, spec):
        return aux  # the static one-hot selection from pallas_prepare


class RandomFourierExpansion(KernelExpansion):
    """Random Fourier features of a stationary kernel (Rahimi-Recht):
    M = 2R paired cos/sin columns, flat weights lambda_m = 1/R, spectral
    base draws stored eps-free in ``GPSpec.omega`` and scaled by
    sqrt(2) * eps inside the feature map (differentiable lengthscales).

    ``kernel`` selects the spectral measure and the exact-kernel oracle:
    'se' (Gaussian frequencies) or 'matern52' (multivariate-t, 5 dof).
    """

    def __init__(self, kernel: str):
        if kernel not in ("se", "matern52"):
            raise ValueError(f"unknown RFF kernel family {kernel!r}")
        self.kernel = kernel
        self.name = f"rff_{kernel}"

    def validate(self, spec) -> None:
        if spec.omega is None:
            raise ValueError(
                f"{self.name} needs spectral base draws on the spec; build "
                f"it with GPSpec.create(..., expansion={self.name!r}, "
                f"num_features=R, seed=...) or GPSpec.create_rff(...)"
            )
        if np.shape(spec.omega) != (np.shape(spec.omega)[0], spec.p):
            raise ValueError(
                f"{self.name}: omega must be (R, p={spec.p}), got "
                f"{np.shape(spec.omega)}"
            )

    def indices(self, spec, p: Optional[int] = None) -> np.ndarray:
        self.validate(spec)
        R = np.shape(spec.omega)[0]
        return np.arange(2 * R, dtype=np.int32).reshape(-1, 1)

    def draw_spec_data(self, p: int, num_features: int, seed: int):
        rng = np.random.default_rng(seed)
        z = rng.standard_normal((num_features, p))
        if self.kernel == "matern52":
            # Matern-nu spectral measure = multivariate-t with 2*nu dof:
            # omega = z * sqrt(2*nu / g), g ~ chi^2_{2*nu}; nu = 5/2
            g = rng.chisquare(5.0, size=(num_features, 1))
            z = z * np.sqrt(5.0 / g)
        return jnp.asarray(z.astype(np.float32))

    def log_eigenvalues(self, idx, spec):
        M = idx.shape[0]
        return jnp.full((M,), -np.log(M / 2.0), jnp.float32)

    def _scaled_freqs(self, spec) -> jax.Array:
        """(R, p) frequencies w_r = sqrt(2) * eps (.) omega_r — the only
        place the lengthscale scaling is applied."""
        return np.sqrt(2.0).astype(np.float32) * spec.eps[None, :] * spec.omega

    def features(self, X, idx, spec):
        W = self._scaled_freqs(spec)                      # (R, p)
        Z = X @ W.T                                       # (N, R)
        return jnp.concatenate([jnp.cos(Z), jnp.sin(Z)], axis=1)

    def exact_kernel(self, Xa, Xb, spec):
        if self.kernel == "se":
            return mercer.k_se_ard(Xa, Xb, spec.eps)
        return mercer.k_matern52_ard(Xa, Xb, spec.eps)

    def pallas_supports(self, spec) -> Optional[str]:
        return None

    def pallas_prepare(self, idx_np, spec):
        return None  # the whole table is data (eps-scaled), built per call

    def tile_fn(self):
        from repro.kernels.rff_phi import rff_tile

        return rff_tile

    def tile_consts(self, spec):
        from repro.kernels.rff_phi import rff_consts_placeholder

        return rff_consts_placeholder()

    def tile_table(self, aux, spec):
        Wt = self._scaled_freqs(spec).T                   # (p, R)
        R = Wt.shape[1]
        phase = jnp.concatenate([
            jnp.zeros((1, R), jnp.float32),
            jnp.full((1, R), -0.5 * np.pi, jnp.float32),
        ], axis=1)                                        # (1, 2R)
        return jnp.concatenate(
            [jnp.concatenate([Wt, Wt], axis=1), phase], axis=0
        )                                                 # (p + 1, 2R)


_EXPANSIONS: dict = {}


def register_expansion(expansion: KernelExpansion) -> None:
    _EXPANSIONS[expansion.name] = expansion


def get_expansion(name: str) -> KernelExpansion:
    try:
        return _EXPANSIONS[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel expansion {name!r}; registered: "
            f"{available_expansions()}"
        ) from None


def available_expansions() -> list:
    return sorted(_EXPANSIONS)


register_expansion(HermiteMercerExpansion())
register_expansion(RandomFourierExpansion("se"))
register_expansion(RandomFourierExpansion("matern52"))
