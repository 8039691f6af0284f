"""GPBank — a fleet of independent GP sessions served as one batched model.

The production analogue of the paper's "cheap posterior on an accelerator"
claim is not one GP but *fleets* of small independent GPs — one per sensor,
user, task, or region — served concurrently.  A Python loop of single-model
calls pays per-call dispatch, per-call kernel launch, and per-call H2D
latency B times; a bank pays them once.

``GPBank`` keeps B fitted sessions resident on the device as ONE stacked
:class:`~repro.core.fagp.FAGPState`:

* leading bank axis on ``chol`` (C, M, M), ``u`` (C, M), ``b`` (C, M),
  ``lam``/``sqrtlam`` (C, M) — the per-tenant factorizations;
* one shared static :class:`~repro.core.fagp.GPSpec` (index set, Mercer
  depth n, backend, hyperparameters) — so every tenant shares one feature
  map and one compiled executable per entry point.

Capacity is fixed at construction: the stack always holds ``capacity``
slots, of which some are *active* (hold a fitted tenant) and the rest hold
the prior state (chol = I, u = b = 0 — a valid "no data yet" posterior).
Membership churn (:meth:`insert` / :meth:`evict`) writes slot leaves with a
*traced* slot index through module-level jitted helpers, so adding or
removing tenants NEVER recompiles the serving executable — the executables
are keyed only on the stack's (capacity, M) shapes.

Entry points (all single compiled calls over the whole fleet):

* :meth:`GPBank.fit`      — B datasets -> B factorizations: one batched
  moment accumulation (``FitBackend.bank_moments``: vmapped scan on the jnp
  backend; a bank grid axis in the streaming fused Pallas kernel on the
  pallas backend) + one batched Cholesky.  Ragged per-tenant N is expressed
  with per-slot row masks on a fixed (B, N, p) stack.
* :meth:`GPBank.mean_var` — a *mixed-tenant* query batch: row q is answered
  by tenant ``tenant_ids[q]``'s posterior, via gather from the stack
  (``FitBackend.bank_mean_var``).
* :meth:`GPBank.update`   — batched rank-k Cholesky ingest for several
  tenants at once (vmapped ``_update_arrays``), scattered back into the
  stack.

* :meth:`GPBank.optimize` — fleet-scale batched hyperparameter learning:
  the (B tenants x R restarts) lane engine (``repro.optim.gp_hyperopt``)
  optimizes every tenant's NLML at once and refits the winners back into
  the stack.  The result is a *heterogeneous* bank: per-slot
  (eps, rho, noise) overlay (``GPBank.hypers``), per-slot eigenvalue rows
  (already stacked), and a serving path that featurizes each query row
  under its own slot's hyperparameters.  Homogeneous banks
  (``hypers is None``) keep every fast path exactly as before.

``bank.router.BankRouter`` turns per-tenant query/observation queues into
the padded fixed-shape batches these entry points want (and tracks
per-tenant staleness for periodic re-optimization).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Hashable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fagp
from repro.core.expansions import get_expansion
from repro.core.fagp import FAGPState, GPSpec
from repro.core.gp import GP
from repro.core.mercer import SEKernelParams

__all__ = ["GPBank"]


# ---------------------------------------------------------------------------
# Module-level jitted kernels.  Deliberately NOT methods: their jit caches
# are keyed on (capacity, M, Q, k) shapes only, so membership churn and
# arbitrary tenant mixes reuse one executable — pinned by
# tests/test_gp_bank.py via _cache_size().
# ---------------------------------------------------------------------------


@jax.jit
def _bank_solve(G, b, loglam, sig2):
    """Batched fit epilogue: raw moments (C, M, M)/(C, M) -> stacked
    (lam, sqrtlam, chol, u).  The scaled system keeps its one home
    (fagp._assemble_scaled_system), vmapped over slots; the Cholesky and the
    mean-weight solves batch natively."""
    Bm, sqrtlam = jax.vmap(
        lambda Gs: fagp._assemble_scaled_system(Gs, loglam, sig2)
    )(G)
    chol = jnp.linalg.cholesky(Bm)
    u = jax.vmap(
        lambda c, d, bs: fagp._solve_mean_weights(c, d, bs, sig2)
    )(chol, sqrtlam, b)
    lam = jnp.broadcast_to(jnp.exp(loglam), sqrtlam.shape)
    return lam, sqrtlam, chol, u


def _bank_update_scatter_impl(chol_s, u_s, b_s, sqrtlam_s, noise_g, slots,
                              Phi_g, y_g, mask_g):
    """Gather slot states, apply the rank-k update per group row, scatter
    back.  Padded rows (mask 0) zero their feature row, which makes the
    rank-1 sweep an identity for them — ragged ingest is a masking detail,
    not a shape change.  A *fully*-masked group (the router's group-axis
    shape padding) writes its gathered values back verbatim: the identity
    sweep is exact only up to sqrt rounding, and an untouched tenant must
    not drift by ulps per serving round.  ``noise_g`` (G,) is per group —
    heterogeneous banks carry per-slot noise; homogeneous banks broadcast
    the shared value.

    Jitted twice below: the plain variant, and a buffer-donating variant
    for pipelined serving loops that own their bank exclusively
    (dispatch-ahead ingest reuses the old stack's device memory instead
    of doubling it; donation is a no-op on backends without support,
    e.g. CPU)."""
    Phi_g = Phi_g * mask_g[..., None]
    y_g = y_g * mask_g
    ch, bb, uu = jax.vmap(
        lambda c, bm, d, s, P, y: fagp._update_arrays(c, bm, d, s, P, y)
    )(chol_s[slots], b_s[slots], sqrtlam_s[slots], noise_g, Phi_g, y_g)
    real = jnp.max(mask_g, axis=1) > 0                  # (G,) any live row?
    ch = jnp.where(real[:, None, None], ch, chol_s[slots])
    uu = jnp.where(real[:, None], uu, u_s[slots])
    bb = jnp.where(real[:, None], bb, b_s[slots])
    return (chol_s.at[slots].set(ch), u_s.at[slots].set(uu),
            b_s.at[slots].set(bb))


_bank_update_scatter = jax.jit(_bank_update_scatter_impl)
_bank_update_scatter_donated = jax.jit(
    _bank_update_scatter_impl, donate_argnums=(0, 1, 2)
)

# relative positive-definiteness guard for the rank-1 downdate sweep: a
# pivot whose downdated square drops below this fraction of its original
# square is declared lost (f32 eps is ~1.2e-7; anything this small is
# noise-dominated and the refit fallback takes over)
_DOWNDATE_TOL = 1e-6


def _chol_rank1_downdate(L: jax.Array, w: jax.Array):
    """Cholesky of L L^T - w w^T, O(M^2) — the mirror of
    ``fagp._chol_rank1_update``'s LINPACK sweep with hyperbolic instead of
    Givens rotations.  Unlike additions, downdates can LOSE positive
    definiteness (w outside the column space, or f32 cancellation);
    returns ``(L', ok)`` where ``ok=False`` flags a pivot that went
    nonpositive — the caller must discard L' and refit from retained data.
    A zero w (masked row) is an exact identity: r = |Lkk|, c = 1, s = 0."""
    M = L.shape[0]
    ar = jnp.arange(M)

    def step(carry, k):
        L, w, ok = carry
        Lkk = L[k, k]
        wk = w[k]
        r2 = Lkk * Lkk - wk * wk
        ok = ok & (r2 > _DOWNDATE_TOL * Lkk * Lkk)
        r = jnp.sqrt(jnp.maximum(r2, jnp.float32(1e-30)))
        c = r / Lkk
        s = wk / Lkk
        col = L[:, k]
        below = ar > k
        newcol = jnp.where(below, (col - s * w) / c, col).at[k].set(r)
        w = jnp.where(below, c * w - s * newcol, w)
        return (L.at[:, k].set(newcol), w, ok), None

    (L, _, ok), _ = jax.lax.scan(step, (L, w, jnp.bool_(True)), ar)
    return L, ok


def _downdate_arrays(chol, b, sqrtlam, noise, Phi_rm, y_rm):
    """Array-level rank-K downdate core: (chol, b) -> (chol', b', u', ok).

    Removes K previously-absorbed rows from the factorization —
    B' = B - sum_k v_k v_k^T with v_k = D phi_k / sigma — via sequential
    rank-1 hyperbolic sweeps (there is no safe refactorization shortcut:
    forming B' by subtraction and re-Cholesky-ing silently NaNs on lost
    positive definiteness, while the sweep detects it per pivot).  ``ok``
    is False when ANY sweep lost a pivot; the outputs are then garbage by
    contract and the caller falls back to a masked refit from the retained
    window."""
    sig2 = noise**2
    W = Phi_rm * sqrtlam[None, :] / noise

    def one(carry, w):
        L, ok = carry
        L2, ok2 = _chol_rank1_downdate(L, w)
        return (L2, ok & ok2), None

    (chol, ok), _ = jax.lax.scan(one, (chol, jnp.bool_(True)), W)
    b = b - jnp.matmul(Phi_rm.T, y_rm, precision=jax.lax.Precision.HIGHEST)
    u = fagp._solve_mean_weights(chol, sqrtlam, b, sig2)
    return chol, b, u, ok


@jax.jit
def _bank_downdate_scatter(chol_s, u_s, b_s, sqrtlam_s, noise_g, slots,
                           Phi_g, y_g, mask_g):
    """The downdate mirror of ``_bank_update_scatter``: gather slot
    states, remove the masked rank-k rows per group, scatter back.  Groups
    that lost positive definiteness (and fully-masked padding groups)
    write their gathered values back VERBATIM — a failed downdate must
    leave the slot untouched so the refit fallback starts from consistent
    state.  Returns the stacked leaves plus a (G,) ``ok`` flag per group
    (padding groups report ok: nothing to remove succeeded trivially)."""
    Phi_g = Phi_g * mask_g[..., None]
    y_g = y_g * mask_g
    ch, bb, uu, ok = jax.vmap(_downdate_arrays)(
        chol_s[slots], b_s[slots], sqrtlam_s[slots], noise_g, Phi_g, y_g
    )
    real = jnp.max(mask_g, axis=1) > 0
    good = ok & real
    ch = jnp.where(good[:, None, None], ch, chol_s[slots])
    uu = jnp.where(good[:, None], uu, u_s[slots])
    bb = jnp.where(good[:, None], bb, b_s[slots])
    return (chol_s.at[slots].set(ch), u_s.at[slots].set(uu),
            b_s.at[slots].set(bb), ok | ~real)


@jax.jit
def _bank_refit_scatter(chol_s, u_s, b_s, lam_s, sqrtlam_s, slots,
                        Xg, yg, maskg, eps_g, rho_g, noise_g, spec, idx):
    """Masked refit of selected slots from retained window data, scattered
    back into the stack — the fallback leg of sliding-window forgetting
    (and a general repair path).  Rides ``_bank_hetero_refit`` so every
    group refits under its own slot's hyperparameters (identical to the
    shared values in a homogeneous bank).  Fully-masked padding groups
    write their gathered values back verbatim, so the group axis can be
    padded to a fixed shape bucket without touching real slots."""
    lam, sqrtlam, chol, u, b = _bank_hetero_refit(
        Xg, yg, maskg, eps_g, rho_g, noise_g, spec, idx
    )
    real = jnp.max(maskg, axis=1) > 0
    chol = jnp.where(real[:, None, None], chol, chol_s[slots])
    u = jnp.where(real[:, None], u, u_s[slots])
    b = jnp.where(real[:, None], b, b_s[slots])
    lam = jnp.where(real[:, None], lam, lam_s[slots])
    sqrtlam = jnp.where(real[:, None], sqrtlam, sqrtlam_s[slots])
    return (chol_s.at[slots].set(chol), u_s.at[slots].set(u),
            b_s.at[slots].set(b), lam_s.at[slots].set(lam),
            sqrtlam_s.at[slots].set(sqrtlam))


@jax.jit
def _write_slot(chol_s, u_s, b_s, lam_s, sqrtlam_s, slot, chol, u, b, lam,
                sqrtlam):
    """Write one tenant's leaves at a *traced* slot index: insert/evict of
    any slot hit the same executable.  Writes the eigenvalue rows too —
    identical to the shared values in a homogeneous bank, per-tenant in a
    heterogeneous one (after :meth:`GPBank.optimize`)."""
    return (chol_s.at[slot].set(chol), u_s.at[slot].set(u),
            b_s.at[slot].set(b), lam_s.at[slot].set(lam),
            sqrtlam_s.at[slot].set(sqrtlam))


@jax.jit
def _hetero_gathered_mean_var(stack, binv, slots, Xq, eps_s, rho_s):
    """Mixed-tenant serving under PER-SLOT hyperparameters: query row q is
    featurized under slot ``slots[q]``'s own (eps, rho) — one vmapped jnp
    feature map per row (per-row feature constants rule out the shared
    backend kernel launch; correctness-first fallback, one executable per
    (Q, p) shape), then the same gathered posterior as the homogeneous
    path."""
    spec = stack.spec

    def row(x, e, r):
        sp = dataclasses.replace(spec, eps=e, rho=r)
        return fagp._features(x[None], stack.idx, sp)[0]

    Phis = jax.vmap(row)(Xq, eps_s[slots], rho_s[slots])
    return fagp._bank_gathered_posterior(
        binv, stack.u, stack.sqrtlam, slots, Phis
    )


@jax.jit
def _hetero_group_features(stack, Xg, eps_g, rho_g):
    """(G, k, M) update-group features, each group under its own slot's
    hyperparameters."""
    spec = stack.spec

    def grp(X, e, r):
        sp = dataclasses.replace(spec, eps=e, rho=r)
        return fagp._features(X, stack.idx, sp)

    return jax.vmap(grp)(Xg, eps_g, rho_g)


@jax.jit
def _bank_hetero_refit(Xb, yb, maskb, eps_b, rho_b, noise_b, spec, idx):
    """Batched refit of B tenants, each under ITS OWN hyperparameters (the
    epilogue of :meth:`GPBank.optimize`): per-tenant streamed moments
    through the backend registry hook (vmapped — the pallas fused kernel
    batches via its grid, the jnp scan via vmap; no N x M Phi either way),
    then the batched scaled solve.  Returns stacked
    (lam, sqrtlam, chol, u, b)."""

    def one(X, y, m, e, r, s):
        sp = dataclasses.replace(spec, eps=e, rho=r, noise=s)
        loglam = get_expansion(sp.expansion).log_eigenvalues(idx, sp)
        G, b = fagp._moments_via_registry(sp, X, y, m)
        Bm, sqrtlam = fagp._assemble_scaled_system(G, loglam, s * s)
        chol = jnp.linalg.cholesky(Bm)
        u = fagp._solve_mean_weights(chol, sqrtlam, b, s * s)
        return jnp.exp(loglam), sqrtlam, chol, u, b

    return jax.vmap(one)(Xb, yb, maskb, eps_b, rho_b, noise_b)


def _fallback_bank_moments(backend):
    """vmap of the single-model moments for backends that do not declare a
    native bank_moments."""
    def f(Xb, yb, spec, idx, aux, block_rows, maskb):
        one = lambda X, y, m: backend.moments(
            X, y, spec, idx, aux, block_rows, m
        )
        return jax.vmap(one)(Xb, yb, maskb)
    return f


def _fallback_bank_mean_var(backend):
    """Gathered posterior on top of the backend's feature map, for backends
    that do not declare a native bank_mean_var."""
    return fagp._gathered_bank_mean_var(backend.features)


def _bank_spec(spec: GPSpec) -> GPSpec:
    """Normalize a spec for bank use: banks are a serving structure and
    never store per-tenant training features, so ``store_train`` is
    downgraded — otherwise every unstacked ``state(t)`` would carry a spec
    claiming stored features while holding ``Phi=None``, and paper-mode
    prediction's 'refit with store_train=True' guidance would loop."""
    return spec.replace(store_train=False) if spec.store_train else spec


def _prior_leaves(loglam: jax.Array, count: int) -> dict:
    """The per-slot leaves of the 'no data yet' state — chol = I,
    u = b = 0, spec eigenvalues — a valid prior posterior (zero mean,
    prior variance).  The ONE definition of an empty slot: ``create``
    builds whole banks from it and ``fit`` pads reserved capacity with it,
    so the fully-masked-slot == fresh-slot invariant cannot drift."""
    M = loglam.shape[0]
    return {
        "lam": jnp.broadcast_to(jnp.exp(loglam), (count, M)),
        "sqrtlam": jnp.broadcast_to(jnp.exp(0.5 * loglam), (count, M)),
        "chol": jnp.broadcast_to(jnp.eye(M, dtype=jnp.float32),
                                 (count, M, M)),
        "u": jnp.zeros((count, M), jnp.float32),
        "b": jnp.zeros((count, M), jnp.float32),
    }


def _check_single_task_with_b(state: FAGPState, who: str) -> None:
    if state.u.ndim != 1:
        raise ValueError(
            f"{who}: multi-output states (T={state.n_tasks}) cannot join a "
            f"bank; banks batch over tenants, one task each"
        )
    if state.b is None:
        raise ValueError(
            f"{who}: state lacks the raw moment vector b (produced by a "
            f"pre-PR-1 fit path); refit before inserting"
        )


def _check_bankable(state: FAGPState, spec: GPSpec, who: str) -> None:
    """A state can join a HOMOGENEOUS bank iff it was factorized under the
    bank's shared spec (structure AND hyperparameters, including any RFF
    spectral draws) and is single-output with the raw moment vector
    present."""
    fagp._check_spec_regenerates_idx(state, spec)
    try:
        fagp._check_hypers_match(state, spec, who)
    except ValueError as e:
        raise ValueError(
            f"{e}; a bank shares one feature map and one eigenvalue "
            f"scaling across all tenants — refit the tenant under the "
            f"bank spec"
        ) from None
    _check_single_task_with_b(state, who)


def _check_bankable_hetero(state: FAGPState, spec: GPSpec, who: str) -> None:
    """A heterogeneous bank (per-slot hyperparameters, produced by
    :meth:`GPBank.optimize`) admits any tenant sharing the bank's expansion
    STRUCTURE — eps/rho/noise may differ per slot, but the expansion
    family, truncation and any RFF spectral draws stay bank-wide (they
    define the shared index table and, for RFF, the shared base
    frequencies)."""
    if state.spec is None:
        raise ValueError(
            f"{who}: state has no baked GPSpec; attach one with "
            f"state.with_spec(spec) before inserting"
        )
    for f in fagp._STRUCTURAL_FIELDS:
        if getattr(state.spec, f) != getattr(spec, f):
            raise ValueError(
                f"{who}: spec/state mismatch: state was fitted with "
                f"{state.spec.describe()} but the bank holds "
                f"{spec.describe()}; even a heterogeneous bank shares one "
                f"expansion structure — refit the tenant"
            )
    if not fagp._leaf_equal(state.spec.omega, spec.omega):
        raise ValueError(
            f"{who}: omega differs from the bank's spectral draws; the "
            f"RFF base frequencies are bank structure even in a "
            f"heterogeneous bank — refit the tenant under the bank's draws"
        )
    fagp._check_spec_regenerates_idx(state, state.spec)
    _check_single_task_with_b(state, who)


@dataclasses.dataclass(frozen=True)
class GPBank:
    """A fixed-capacity bank of independent GP sessions (see module doc).

    Construct with :meth:`fit`, :meth:`create`, or :meth:`from_states`; the
    default constructor is internal.  Instances are immutable — mutating
    methods return a new ``GPBank`` sharing the device stack buffers that
    did not change.

    stack:   stacked FAGPState — bank axis on chol/u/b/lam/sqrtlam,
             shared idx/params/spec.
    active:  (capacity,) host-side bool mask of occupied slots.
    slots:   tenant id -> slot index (host-side; insertion order preserved).
    hypers:  None for a homogeneous bank (every tenant shares the spec's
             eps/rho/noise — all fast paths unchanged), or per-slot stacked
             hyperparameters (eps (C, p), rho (C, p), noise (C,)) once
             :meth:`optimize` has learned per-tenant values.  Heterogeneous
             serving featurizes each query row under its own slot's
             hyperparameters (``_hetero_gathered_mean_var``).
    """

    stack: FAGPState
    active: np.ndarray
    slots: Mapping[Hashable, int]
    hypers: Optional[SEKernelParams] = None

    # -- constructors -------------------------------------------------------

    @classmethod
    def create(cls, spec: GPSpec, capacity: int) -> "GPBank":
        """An empty bank: every slot holds the prior state (chol = I,
        u = b = 0 — zero mean, prior variance)."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        spec = _bank_spec(spec)
        fagp._check_backend_support(spec)
        idx = jnp.asarray(spec.indices(spec.p))
        loglam = get_expansion(spec.expansion).log_eigenvalues(idx, spec)
        stack = FAGPState(
            idx=idx, params=spec.params, Phi=None, y=None, spec=spec,
            **_prior_leaves(loglam, capacity),
        )
        return cls(stack=stack, active=np.zeros(capacity, bool), slots={})

    @classmethod
    def fit(
        cls,
        Xb: jax.Array,
        yb: jax.Array,
        spec: GPSpec,
        *,
        mask: Optional[jax.Array] = None,
        tenant_ids: Optional[Sequence[Hashable]] = None,
        capacity: Optional[int] = None,
    ) -> "GPBank":
        """Fit B independent GPs in one batched pass.

        Xb: (B, N, p) stacked inputs; yb: (B, N) stacked targets;
        mask: (B, N) row validity — tenants with fewer than N real rows pad
        to N and mask the padding (ragged N).  ``tenant_ids`` default to
        ``range(B)``; ``capacity`` (>= B) reserves extra prior slots for
        later :meth:`insert` without reshaping the stack.
        """
        Xb = jnp.asarray(Xb)
        yb = jnp.asarray(yb)
        if Xb.ndim != 3 or yb.ndim != 2 or yb.shape != Xb.shape[:2]:
            raise ValueError(
                f"GPBank.fit wants Xb (B, N, p) and yb (B, N); got "
                f"{Xb.shape} and {yb.shape}"
            )
        B, N, p = Xb.shape
        spec = _bank_spec(spec)
        fagp._check_p(spec, p)
        cap = B if capacity is None else int(capacity)
        if cap < B:
            raise ValueError(f"capacity {cap} < number of tenants {B}")
        if tenant_ids is None:
            tenant_ids = range(B)
        tenant_ids = list(tenant_ids)
        if len(tenant_ids) != B or len(set(tenant_ids)) != B:
            raise ValueError(
                f"tenant_ids must be {B} distinct ids, got {tenant_ids!r}"
            )
        if mask is None:
            mask = jnp.ones((B, N), Xb.dtype)
        else:
            mask = jnp.asarray(mask).astype(Xb.dtype)
            if mask.shape != (B, N):
                raise ValueError(
                    f"mask must be (B, N) = {(B, N)}, got {mask.shape}"
                )
        backend = fagp._check_backend_support(spec)
        idx_np = spec.indices(p)
        idx = jnp.asarray(idx_np)
        aux = backend.prepare(idx_np, spec)
        moments = backend.bank_moments or _fallback_bank_moments(backend)
        # small tenants: never let a scan-based moments hook pad each
        # slot's few rows up to the default serving block
        block_rows = min(spec.block_rows, max(1, N))
        G, b = moments(Xb, yb, spec, idx, aux, block_rows, mask)
        loglam = get_expansion(spec.expansion).log_eigenvalues(idx, spec)
        lam, sqrtlam, chol, u = _bank_solve(G, b, loglam, spec.noise**2)
        if cap > B:
            # reserved slots get the prior leaves directly — never pay the
            # O(N M^2) moment pass or the M^3 Cholesky for an empty slot
            prior = _prior_leaves(loglam, cap - B)
            lam = jnp.concatenate([lam, prior["lam"]])
            sqrtlam = jnp.concatenate([sqrtlam, prior["sqrtlam"]])
            chol = jnp.concatenate([chol, prior["chol"]])
            u = jnp.concatenate([u, prior["u"]])
            b = jnp.concatenate([b, prior["b"]])
        stack = FAGPState(
            idx=idx, lam=lam, sqrtlam=sqrtlam, chol=chol, u=u,
            params=spec.params, Phi=None, y=None, b=b, spec=spec,
        )
        active = np.zeros(cap, bool)
        active[:B] = True
        return cls(stack=stack, active=active,
                   slots={t: s for s, t in enumerate(tenant_ids)})

    @classmethod
    def from_states(
        cls,
        states: Mapping[Hashable, Any],
        *,
        capacity: Optional[int] = None,
    ) -> "GPBank":
        """Stack already-fitted sessions (``GP`` or ``FAGPState``) into a
        bank.  All must share one structural spec and one hyperparameter
        set (the bank's shared feature map)."""
        if not states:
            raise ValueError("from_states needs at least one state")
        items = [
            (t, s.state if isinstance(s, GP) else s) for t, s in states.items()
        ]
        spec = items[0][1].spec
        if spec is None:
            raise ValueError(
                "from_states: first state has no baked GPSpec; attach one "
                "with state.with_spec(spec)"
            )
        spec = _bank_spec(spec)
        for t, st in items:
            _check_bankable(st, spec, f"from_states(tenant {t!r})")
        B = len(items)
        cap = B if capacity is None else int(capacity)
        if cap < B:
            raise ValueError(f"capacity {cap} < number of states {B}")
        bank = cls.create(spec, cap)
        stacked = {
            f: jnp.stack([getattr(st, f) for _, st in items])
            for f in ("lam", "sqrtlam", "chol", "u", "b")
        }
        pad = {
            f: jnp.concatenate([stacked[f], getattr(bank.stack, f)[B:]])
            for f in stacked
        }
        stack = dataclasses.replace(bank.stack, **pad)
        active = np.zeros(cap, bool)
        active[:B] = True
        return cls(stack=stack, active=active,
                   slots={t: s for s, (t, _) in enumerate(items)})

    # -- introspection ------------------------------------------------------

    @property
    def spec(self) -> GPSpec:
        return self.stack.spec

    @property
    def capacity(self) -> int:
        return self.stack.u.shape[0]

    @property
    def n_features(self) -> int:
        return self.stack.idx.shape[0]

    @property
    def tenants(self) -> list:
        return list(self.slots)

    def __len__(self) -> int:
        return len(self.slots)

    def __contains__(self, tenant: Hashable) -> bool:
        return tenant in self.slots

    def slot_of(self, tenant: Hashable) -> int:
        try:
            return self.slots[tenant]
        except KeyError:
            raise KeyError(
                f"tenant {tenant!r} is not in this bank (tenants: "
                f"{self.tenants!r})"
            ) from None

    def state(self, tenant: Hashable) -> FAGPState:
        """The tenant's session, unstacked — a normal single-model
        FAGPState usable with every ``fagp``/``GP`` entry point.  In a
        heterogeneous bank the returned state's spec carries the tenant's
        OWN learned hyperparameters."""
        s = self.slot_of(tenant)
        st = dataclasses.replace(
            self.stack,
            lam=self.stack.lam[s], sqrtlam=self.stack.sqrtlam[s],
            chol=self.stack.chol[s], u=self.stack.u[s], b=self.stack.b[s],
        )
        if self.hypers is not None:
            sp = self.spec.replace(
                eps=self.hypers.eps[s], rho=self.hypers.rho[s],
                noise=self.hypers.noise[s],
            )
            st = dataclasses.replace(st, spec=sp, params=sp.params)
        return st

    def _stacked_hypers(self) -> SEKernelParams:
        """Per-slot hyperparameters, materialized: the overlay when
        heterogeneous, the shared spec values broadcast when not."""
        if self.hypers is not None:
            return self.hypers
        sp = self.spec
        C = self.capacity
        return SEKernelParams(
            eps=jnp.broadcast_to(sp.eps, (C,) + sp.eps.shape),
            rho=jnp.broadcast_to(sp.rho, (C,) + sp.rho.shape),
            noise=jnp.broadcast_to(jnp.asarray(sp.noise, jnp.float32), (C,)),
        )

    def states(self) -> dict:
        """All tenants' sessions, unstacked (tenant -> FAGPState)."""
        return {t: self.state(t) for t in self.slots}

    @property
    def _binv(self) -> jax.Array:
        """Per-slot B^{-1} serving cache (C, M, M).  Lazily computed and
        memoized on the instance: GPBank is immutable and every mutating
        method returns a *new* bank, so the cache can never go stale.
        Mutations that know which slots they touched carry the cache
        forward with only those rows refreshed (``_carry_binv_into``)."""
        cached = self.__dict__.get("_binv_cache")
        if cached is None:
            cached = fagp._bank_binv(self.stack.chol)
            object.__setattr__(self, "_binv_cache", cached)
        return cached

    def _carry_binv_into(self, new: "GPBank", slots: jax.Array) -> None:
        """Incremental cache maintenance: a mutation touched only ``slots``
        (possibly one), so if this bank already paid for the full cache,
        refresh those rows and hand the rest forward instead of making the
        next query recompute B^{-1} for the whole capacity."""
        cached = self.__dict__.get("_binv_cache")
        if cached is not None:
            slots = jnp.atleast_1d(slots)
            rows = fagp._bank_binv(new.stack.chol[slots])
            object.__setattr__(
                new, "_binv_cache", cached.at[slots].set(rows)
            )

    def _slots_for(self, tenant_ids) -> jax.Array:
        if isinstance(tenant_ids, (str, bytes)) or not hasattr(
            tenant_ids, "__iter__"
        ):
            raise TypeError(
                "tenant_ids must be a sequence of tenant ids, one per row "
                f"(got a scalar {tenant_ids!r}); for a single-tenant batch "
                "pass [tenant] * len(Xq)"
            )
        return jnp.asarray(
            np.fromiter(
                (self.slot_of(t) for t in tenant_ids), np.int32,
            )
        )

    # -- the batched pipeline ----------------------------------------------

    @staticmethod
    def result_ready(*arrays) -> bool:
        """Have these dispatched results landed?  ``mean_var`` returns
        device arrays that are *futures* under JAX's asynchronous
        dispatch; a pipelined serving loop (``repro.bank.FleetEngine``)
        polls this to harvest completed blocks without ever blocking on
        an unfinished one.  Arrays without readiness introspection (older
        jax, concrete numpy inputs) report ready — the harvest then
        degrades to a blocking conversion, never to a wrong answer."""
        return all(
            ready() for a in arrays
            if (ready := getattr(a, "is_ready", None)) is not None
        )

    def mean_var(self, tenant_ids, Xq: jax.Array):
        """Posterior mean and marginal variance for a MIXED-tenant query
        batch: row q of ``Xq`` (Q, p) is answered by ``tenant_ids[q]``'s
        posterior.  One compiled call for the whole fleet."""
        Xq = jnp.asarray(Xq)
        slots = self._slots_for(tenant_ids)
        if slots.shape[0] != Xq.shape[0]:
            raise ValueError(
                f"one tenant id per query row: got {slots.shape[0]} ids "
                f"for {Xq.shape[0]} rows"
            )
        backend = fagp._check_backend_support(self.spec)
        if self.hypers is not None:
            return _hetero_gathered_mean_var(
                self.stack, self._binv, slots, Xq,
                self.hypers.eps, self.hypers.rho,
            )
        aux = fagp._backend_aux(backend, self.stack.idx, self.spec)
        fn = backend.bank_mean_var or _fallback_bank_mean_var(backend)
        return fn(self.stack, self._binv, slots, Xq, aux)

    def update(self, tenant_ids, Xk: jax.Array, yk: jax.Array,
               mask: Optional[jax.Array] = None) -> "GPBank":
        """Batched rank-k ingest: group g absorbs (Xk[g], yk[g]) into tenant
        ``tenant_ids[g]``'s factorization — vmapped rank-k Cholesky update,
        scattered back into the stack.  ``mask`` (G, k) zeroes padded rows
        (ragged ingest).  Tenants must be distinct within one call (the
        scatter would race); the router serializes duplicates into rounds."""
        Xk = jnp.asarray(Xk)
        yk = jnp.asarray(yk)
        if Xk.ndim != 3 or yk.shape != Xk.shape[:2]:
            raise ValueError(
                f"GPBank.update wants Xk (G, k, p) and yk (G, k); got "
                f"{Xk.shape} and {yk.shape}"
            )
        ids = list(tenant_ids)
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"duplicate tenant in one update batch ({ids!r}): the "
                f"scattered writes would collide — split into rounds "
                f"(BankRouter.ingest does this)"
            )
        if len(ids) != Xk.shape[0]:
            raise ValueError(
                f"one tenant id per update group: got {len(ids)} ids for "
                f"{Xk.shape[0]} groups"
            )
        return self._update_at_slots(self._slots_for(ids), Xk, yk, mask)

    def _update_at_slots(self, slots: jax.Array, Xk: jax.Array,
                         yk: jax.Array,
                         mask: Optional[jax.Array] = None,
                         donate: bool = False) -> "GPBank":
        """Slot-addressed core of :meth:`update`.  Also the router's
        fixed-shape entry: a fully-masked group is an exact identity update
        (zeroed feature rows make every rank-1 sweep a no-op), so the
        router pads the group axis to a shape bucket with masked groups
        aimed at distinct unused slots — bounding the number of compiled
        update executables by log2(capacity) instead of one per distinct
        tenant-mix size.  Slots must be distinct (scatter would race).

        ``donate=True`` routes through the buffer-donating executable:
        the pre-update chol/u/b stack buffers are handed to XLA for reuse
        — THIS bank (and any older bank sharing those buffers) must not
        be touched afterwards.  Reserved for serving loops that own their
        bank exclusively (``BankRouter(donate_updates=True)``)."""
        G, k, p = Xk.shape
        fagp._check_p(self.spec, p)
        if mask is None:
            mask = jnp.ones((G, k), Xk.dtype)
        else:
            mask = jnp.asarray(mask).astype(Xk.dtype)
            if mask.shape != (G, k):
                raise ValueError(
                    f"mask must be (G, k) = {(G, k)}, got {mask.shape} — a "
                    f"broadcastable mask would silently drop rows from "
                    f"every group"
                )
        backend = fagp._check_backend_support(self.spec)
        if self.hypers is not None:
            Phi_g = _hetero_group_features(
                self.stack, Xk, self.hypers.eps[slots],
                self.hypers.rho[slots],
            )
            noise_g = self.hypers.noise[slots]
        else:
            aux = fagp._backend_aux(backend, self.stack.idx, self.spec)
            Phi_g = backend.features(
                Xk.reshape(G * k, p), self.spec, self.stack.idx, aux,
            ).reshape(G, k, -1)
            noise_g = jnp.broadcast_to(
                jnp.asarray(self.stack.params.noise, jnp.float32), (G,)
            )
        scatter = (_bank_update_scatter_donated if donate
                   else _bank_update_scatter)
        chol, u, b = scatter(
            self.stack.chol, self.stack.u, self.stack.b, self.stack.sqrtlam,
            noise_g, slots, Phi_g, yk, mask,
        )
        stack = dataclasses.replace(self.stack, chol=chol, u=u, b=b)
        new = dataclasses.replace(self, stack=stack)
        self._carry_binv_into(new, slots)
        return new

    # -- sliding-window forgetting (rank-k downdate + refit fallback) -------

    def downdate(self, tenant_ids, Xk: jax.Array, yk: jax.Array,
                 mask: Optional[jax.Array] = None):
        """Batched rank-k FORGET: group g removes previously-absorbed rows
        (Xk[g], yk[g]) from tenant ``tenant_ids[g]``'s factorization — the
        mirror of :meth:`update` via hyperbolic rank-1 downdate sweeps.
        ``mask`` (G, k) zeroes padded rows.  Tenants must be distinct
        within one call (the scatter would race).

        Returns ``(bank, ok)`` where ``ok`` is a host (G,) bool array:
        groups whose downdate lost positive definiteness kept their slot
        UNCHANGED (ok False) — re-factorize them from retained data with
        :meth:`refit_window`.  ``TieredBank.age`` drives both legs."""
        Xk = jnp.asarray(Xk)
        yk = jnp.asarray(yk)
        if Xk.ndim != 3 or yk.shape != Xk.shape[:2]:
            raise ValueError(
                f"GPBank.downdate wants Xk (G, k, p) and yk (G, k); got "
                f"{Xk.shape} and {yk.shape}"
            )
        ids = list(tenant_ids)
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"duplicate tenant in one downdate batch ({ids!r}): the "
                f"scattered writes would collide — split into rounds"
            )
        if len(ids) != Xk.shape[0]:
            raise ValueError(
                f"one tenant id per downdate group: got {len(ids)} ids "
                f"for {Xk.shape[0]} groups"
            )
        return self._downdate_at_slots(self._slots_for(ids), Xk, yk, mask)

    def _downdate_at_slots(self, slots: jax.Array, Xk: jax.Array,
                           yk: jax.Array,
                           mask: Optional[jax.Array] = None):
        """Slot-addressed core of :meth:`downdate` — the fixed-shape entry
        for ``TieredBank.age``'s bucketed group axis (fully-masked padding
        groups on distinct slots are exact identity writes and report
        ok)."""
        G, k, p = Xk.shape
        fagp._check_p(self.spec, p)
        if mask is None:
            mask = jnp.ones((G, k), Xk.dtype)
        else:
            mask = jnp.asarray(mask).astype(Xk.dtype)
            if mask.shape != (G, k):
                raise ValueError(
                    f"mask must be (G, k) = {(G, k)}, got {mask.shape}"
                )
        backend = fagp._check_backend_support(self.spec)
        if self.hypers is not None:
            Phi_g = _hetero_group_features(
                self.stack, Xk, self.hypers.eps[slots],
                self.hypers.rho[slots],
            )
            noise_g = self.hypers.noise[slots]
        else:
            aux = fagp._backend_aux(backend, self.stack.idx, self.spec)
            Phi_g = backend.features(
                Xk.reshape(G * k, p), self.spec, self.stack.idx, aux,
            ).reshape(G, k, -1)
            noise_g = jnp.broadcast_to(
                jnp.asarray(self.stack.params.noise, jnp.float32), (G,)
            )
        chol, u, b, ok = _bank_downdate_scatter(
            self.stack.chol, self.stack.u, self.stack.b, self.stack.sqrtlam,
            noise_g, slots, Phi_g, yk, mask,
        )
        stack = dataclasses.replace(self.stack, chol=chol, u=u, b=b)
        new = dataclasses.replace(self, stack=stack)
        self._carry_binv_into(new, slots)
        return new, np.asarray(ok)

    def refit_window(self, tenant_ids, Xw: jax.Array, yw: jax.Array,
                     mask: Optional[jax.Array] = None) -> "GPBank":
        """Re-factorize ``tenant_ids`` from scratch on their RETAINED
        window data (Xw (G, W, p), yw (G, W), mask (G, W) for ragged
        windows) — each under its own slot's hyperparameters, per-slot
        eigenvalue rows rewritten.  The fallback for downdates that lost
        positive definiteness, and the exact semantic reference the
        downdate is gated against (<= 1e-5, benchmarks/tenant_churn.py)."""
        Xw = jnp.asarray(Xw)
        yw = jnp.asarray(yw)
        if Xw.ndim != 3 or yw.shape != Xw.shape[:2]:
            raise ValueError(
                f"GPBank.refit_window wants Xw (G, W, p) and yw (G, W); "
                f"got {Xw.shape} and {yw.shape}"
            )
        ids = list(tenant_ids)
        if len(set(ids)) != len(ids):
            raise ValueError(
                f"duplicate tenant in one refit batch ({ids!r})"
            )
        if len(ids) != Xw.shape[0]:
            raise ValueError(
                f"one tenant id per refit group: got {len(ids)} ids for "
                f"{Xw.shape[0]} groups"
            )
        return self._refit_at_slots(self._slots_for(ids), Xw, yw, mask)

    def _refit_at_slots(self, slots: jax.Array, Xw: jax.Array,
                        yw: jax.Array,
                        mask: Optional[jax.Array] = None) -> "GPBank":
        """Slot-addressed core of :meth:`refit_window` (fixed-shape entry;
        fully-masked padding groups leave their slots untouched)."""
        G, W, p = Xw.shape
        fagp._check_p(self.spec, p)
        if mask is None:
            mask = jnp.ones((G, W), Xw.dtype)
        else:
            mask = jnp.asarray(mask).astype(Xw.dtype)
            if mask.shape != (G, W):
                raise ValueError(
                    f"mask must be (G, W) = {(G, W)}, got {mask.shape}"
                )
        hyp = self._stacked_hypers()
        spec_r = self.spec.replace(
            block_rows=min(self.spec.block_rows, max(1, W))
        )
        st = self.stack
        chol, u, b, lam, sqrtlam = _bank_refit_scatter(
            st.chol, st.u, st.b, st.lam, st.sqrtlam, slots,
            Xw, yw, mask, hyp.eps[slots], hyp.rho[slots], hyp.noise[slots],
            spec_r, st.idx,
        )
        stack = dataclasses.replace(st, chol=chol, u=u, b=b, lam=lam,
                                    sqrtlam=sqrtlam)
        new = dataclasses.replace(self, stack=stack)
        self._carry_binv_into(new, slots)
        return new

    # -- membership churn (never recompiles: fixed capacity, traced slot) ---

    def insert(self, tenant: Hashable, source) -> "GPBank":
        """Add a tenant into a free slot.  ``source`` is a fitted ``GP`` /
        ``FAGPState`` sharing the bank's spec, or an ``(X, y)`` tuple to be
        fitted under it.  Raises when full or when the id is taken."""
        if tenant in self.slots:
            raise ValueError(f"tenant {tenant!r} already in the bank")
        free = np.flatnonzero(~self.active)
        if free.size == 0:
            raise ValueError(
                f"bank is full ({self.capacity} slots); evict a tenant or "
                f"rebuild with a larger capacity"
            )
        if isinstance(source, tuple):
            X, y = source
            st = fagp.fit(jnp.asarray(X), jnp.asarray(y), self.spec)
        else:
            st = source.state if isinstance(source, GP) else source
        if self.hypers is None:
            _check_bankable(st, self.spec, f"insert({tenant!r})")
        else:
            _check_bankable_hetero(st, self.spec, f"insert({tenant!r})")
        slot = int(free[0])
        chol, u, b, lam, sqrtlam = _write_slot(
            self.stack.chol, self.stack.u, self.stack.b, self.stack.lam,
            self.stack.sqrtlam, jnp.int32(slot), st.chol, st.u, st.b,
            st.lam, st.sqrtlam,
        )
        stack = dataclasses.replace(self.stack, chol=chol, u=u, b=b,
                                    lam=lam, sqrtlam=sqrtlam)
        hypers = self.hypers
        if hypers is not None:
            hp = st.spec  # guaranteed by _check_bankable_hetero
            hypers = SEKernelParams(
                eps=hypers.eps.at[slot].set(hp.eps),
                rho=hypers.rho.at[slot].set(hp.rho),
                noise=hypers.noise.at[slot].set(hp.noise),
            )
        active = self.active.copy()
        active[slot] = True
        slots = dict(self.slots)
        slots[tenant] = slot
        new = dataclasses.replace(self, stack=stack, active=active,
                                  slots=slots, hypers=hypers)
        self._carry_binv_into(new, jnp.int32(slot))
        return new

    def evict(self, tenant: Hashable) -> "GPBank":
        """Remove a tenant; its slot is reset to the prior state (under the
        bank spec's own hyperparameters) and becomes reusable by the next
        :meth:`insert` — same executable either way."""
        slot = self.slot_of(tenant)
        loglam = get_expansion(self.spec.expansion).log_eigenvalues(
            self.stack.idx, self.spec
        )
        prior = _prior_leaves(loglam, 1)
        chol, u, b, lam, sqrtlam = _write_slot(
            self.stack.chol, self.stack.u, self.stack.b, self.stack.lam,
            self.stack.sqrtlam, jnp.int32(slot), prior["chol"][0],
            prior["u"][0], prior["b"][0], prior["lam"][0],
            prior["sqrtlam"][0],
        )
        stack = dataclasses.replace(self.stack, chol=chol, u=u, b=b,
                                    lam=lam, sqrtlam=sqrtlam)
        hypers = self.hypers
        if hypers is not None:
            sp = self.spec
            hypers = SEKernelParams(
                eps=hypers.eps.at[slot].set(sp.eps),
                rho=hypers.rho.at[slot].set(sp.rho),
                noise=hypers.noise.at[slot].set(
                    jnp.asarray(sp.noise, jnp.float32)
                ),
            )
        active = self.active.copy()
        active[slot] = False
        slots = {t: s for t, s in self.slots.items() if t != tenant}
        new = dataclasses.replace(self, stack=stack, active=active,
                                  slots=slots, hypers=hypers)
        self._carry_binv_into(new, jnp.int32(slot))
        return new

    # -- fleet-scale hyperparameter optimization ----------------------------

    def optimize(
        self,
        Xb: jax.Array,
        yb: jax.Array,
        *,
        tenant_ids: Optional[Sequence[Hashable]] = None,
        mask: Optional[jax.Array] = None,
        restarts: int = 4,
        steps: int = 100,
        lr: float = 5e-2,
        tol: Optional[float] = None,
        jitter: float = 0.3,
        seed: int = 0,
        callback=None,
        metrics=None,
        tracer=None,
    ) -> "GPBank":
        """Learn per-tenant hyperparameters for the whole fleet in one
        batched run, then refit the winners back into the stacked state.

        Runs the (B tenants x R restarts) lane engine
        (``repro.optim.gp_hyperopt.optimize_fleet``): every restart of every
        tenant is stepped by ONE compiled AdamW step per iteration — a
        Python loop of per-tenant ``GP.optimize`` runs pays per-step
        dispatch B times and lands on EXACTLY the same hyperparameters (the
        per-tenant lane math is bit-identical by construction; the <= 1e-5
        parity gate is asserted in benchmarks/gp_hyperopt.py).

        Xb (B, N, p) / yb (B, N) carry each tenant's training data in the
        row order of ``tenant_ids`` (default: every active tenant in
        insertion order); ``mask`` (B, N) expresses ragged per-tenant N.
        ``restarts`` log-space jittered inits per tenant, best selected by
        final NLML; ``tol`` freezes converged lanes (no recompiles).

        Returns a new HETEROGENEOUS bank: the optimized slots hold
        factorizations under their own learned (eps, rho, noise) — per-slot
        eigenvalue rows were already stacked — and serving gathers each
        query row's features under its slot's hyperparameters.  A bank that
        is already heterogeneous re-optimizes starting from each tenant's
        current values.

        ``metrics`` / ``tracer`` (``repro.obs``) forward to
        ``optimize_fleet``, which reports per-round progress through the
        existing callback contract (composed with any user ``callback``).
        """
        from repro.optim.gp_hyperopt import optimize_fleet

        Xb = jnp.asarray(Xb)
        yb = jnp.asarray(yb)
        if Xb.ndim != 3 or yb.ndim != 2 or yb.shape != Xb.shape[:2]:
            raise ValueError(
                f"GPBank.optimize wants Xb (B, N, p) and yb (B, N); got "
                f"{Xb.shape} and {yb.shape}"
            )
        B, N, p = Xb.shape
        fagp._check_p(self.spec, p)
        if tenant_ids is None:
            tenant_ids = self.tenants
        ids = list(tenant_ids)
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate tenant in optimize batch ({ids!r})")
        if len(ids) != B:
            raise ValueError(
                f"one tenant id per data row: got {len(ids)} ids for {B} "
                f"rows"
            )
        slots = self._slots_for(ids)
        if mask is not None:
            mask = jnp.asarray(mask).astype(Xb.dtype)
            if mask.shape != (B, N):
                raise ValueError(
                    f"mask must be (B, N) = {(B, N)}, got {mask.shape}"
                )
        init = None
        if self.hypers is not None:
            init = {
                "eps": self.hypers.eps[slots],
                "rho": self.hypers.rho[slots],
                "noise": self.hypers.noise[slots],
            }
        res = optimize_fleet(
            Xb, yb, self.spec, mask=mask, restarts=restarts, steps=steps,
            lr=lr, tol=tol, jitter=jitter, seed=seed, init=init,
            callback=callback, metrics=metrics, tracer=tracer,
        )
        maskb = (jnp.ones((B, N), Xb.dtype) if mask is None else mask)
        spec_r = self.spec.replace(
            block_rows=min(self.spec.block_rows, max(1, N))
        )
        lam, sqrtlam, chol, u, b = _bank_hetero_refit(
            Xb, yb, maskb, res.eps, res.rho, res.noise, spec_r,
            self.stack.idx,
        )
        st = self.stack
        stack = dataclasses.replace(
            st,
            lam=st.lam.at[slots].set(lam),
            sqrtlam=st.sqrtlam.at[slots].set(sqrtlam),
            chol=st.chol.at[slots].set(chol),
            u=st.u.at[slots].set(u),
            b=st.b.at[slots].set(b),
        )
        hyp = self._stacked_hypers()
        hyp = SEKernelParams(
            eps=hyp.eps.at[slots].set(res.eps),
            rho=hyp.rho.at[slots].set(res.rho),
            noise=hyp.noise.at[slots].set(res.noise),
        )
        new = dataclasses.replace(self, stack=stack, hypers=hyp)
        self._carry_binv_into(new, slots)
        return new
