"""Bank-axis sharding: one GPBank fleet spread across a device mesh.

The stacked ``FAGPState``'s leading capacity axis is embarrassingly
parallel — every slot owns an independent (chol, u, b) factorization — so
a ``bank`` mesh axis shards it with ZERO cross-shard collectives on the
serving hot path (Chen et al.'s parallel low-rank GP regression distributes
exactly this Gram/weights summary structure across workers).

Design:

  * ``ShardedGPBank`` mirrors :class:`~repro.bank.bank.GPBank`'s public
    surface (fit / mean_var / update / downdate / refit_window / insert /
    evict / state / slots ...) so ``BankRouter``, ``FleetEngine`` and
    ``TieredBank`` drive it unchanged.  Slots stay GLOBAL ids; shard
    ``slot // shard_capacity`` owns local row ``slot % shard_capacity``.
  * Every batched executable is a module-level jit (mesh static) wrapping
    ONE ``shard_map`` whose body reuses the resident bank's array cores
    (``_bank_update_scatter_impl``, ``_bank_downdate_scatter``,
    ``_bank_refit_scatter``, ``fagp._bank_gathered_posterior``) on the
    shard-local leaves — the math has one home, this module only places it.
  * Mixed-shard batches are grouped host-side: rows/groups are packed per
    shard and padded to a shared pow2 rung (``per-shard microbatch
    buckets``), so one hot shard never pad-inflates the others and the
    executable count stays O(log capacity) — exactly the resident bank's
    zero-recompile contract, per shard.
  * ``insert``/``evict``/``rebalance`` ride one traced-global-slot write
    executable (a masked ``axis_index`` write per shard), so membership
    churn — including cross-shard moves — never recompiles.
  * The serving B^{-1} cache is maintained EAGERLY: every mutating
    executable refreshes the touched rows shard-locally, so serving never
    pays a full-capacity recompute and the cache never leaves its shard.
  * Composes with the v2 row-sharding of ``core.distributed`` as a 2-D
    ``(bank, data)`` mesh: ``fit`` additionally shards the N row axis over
    ``data`` and combines shard-partial moments with one psum over 'data'
    (fit-only; serving stays collective-free).

Spec-local rebuild glue (``spec_local`` / ``omega_args``) is shared with
the v2 schedules via ``core.shardspec`` — the same leaves-in, spec-out
discipline keeps outer tracers from leaking into shard_map bodies.

Homogeneous banks only: per-slot hyperparameter overlays
(:meth:`GPBank.optimize`) serve through per-row featurization that has no
shard-local fast path yet — convert with :meth:`ShardedGPBank.to_bank`
first.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Hashable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fagp, shardspec
from repro.core.expansions import get_expansion
from repro.core.fagp import FAGPState, GPSpec
from repro.core.gp import GP
from repro.core.mercer import SEKernelParams

from . import bank as bank_mod
from .bank import (
    GPBank,
    _bank_solve,
    _bank_spec,
    _check_bankable,
    _prior_leaves,
)

__all__ = ["ShardedGPBank"]


def _bank_axis_specs(mesh) -> tuple:
    """(P('bank'), P()) pair for a mesh whose first axis is 'bank' — any
    extra axes (the v2 'data' axis) replicate bank-stacked leaves."""
    if "bank" not in mesh.axis_names:
        raise ValueError(
            f"sharded bank needs a mesh axis named 'bank'; got axes "
            f"{mesh.axis_names!r}"
        )
    return P("bank"), P()


def _leaf_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, P("bank"))


def _pow2(n: int) -> int:
    return 1 << (max(1, int(n)) - 1).bit_length()


# ---------------------------------------------------------------------------
# host-side per-shard grouping (the padding policy in one place)
# ---------------------------------------------------------------------------


def _group_rows(gslots: np.ndarray, C_l: int, S: int, cap=None):
    """Pack mixed-shard rows into the (S, Q_s) per-shard layout.

    Returns ``(lslots (S*Q_s,) int32, pos (n,) int64, Q_s)`` where row i of
    the caller's batch lands at flat position ``pos[i]`` and padding rows
    aim at local slot 0 (their results are discarded, duplicate gathers
    are safe).  ``Q_s`` is the pow2 rung of the busiest shard — the
    per-shard microbatch bucket (optionally capped, for scatter callers
    whose padding needs untargeted slots)."""
    n = len(gslots)
    shard = gslots // C_l
    counts = np.bincount(shard, minlength=S)
    Qs = _pow2(counts.max()) if n else 1
    if cap is not None:
        Qs = min(int(cap), Qs)
    order = np.argsort(shard, kind="stable")
    start = np.searchsorted(shard[order], np.arange(S))
    ranks = np.empty(n, np.int64)
    ranks[order] = np.arange(n) - start[shard[order]]
    pos = shard.astype(np.int64) * Qs + ranks
    lslots = np.zeros(S * Qs, np.int32)
    lslots[pos] = (gslots % C_l).astype(np.int32)
    return lslots, pos, Qs


def _group_slots(gslots: np.ndarray, C_l: int, S: int):
    """Per-shard grouping for scatter ops (update/downdate/refit): slots
    must be DISTINCT within a shard, so padding groups aim at the lowest
    local slots not targeted by a real group in that shard (fully-masked
    groups are exact identity writes, active or not)."""
    lslots, pos, Qs = _group_rows(gslots, C_l, S, cap=C_l)
    used = [set() for _ in range(S)]
    for g, l in zip(gslots // C_l, gslots % C_l):
        used[g].add(int(l))
    for s in range(S):
        fill = (l for l in range(C_l) if l not in used[s])
        n_real = len(used[s])
        for j in range(n_real, Qs):
            lslots[s * Qs + j] = next(fill)
    return lslots, pos, Qs


# ---------------------------------------------------------------------------
# batched shard-local executables (module-level: compiled once per shape)
# ---------------------------------------------------------------------------


def _binv_rows(chol_rows):
    """(G, M, M) Cholesky rows -> B^{-1} rows (the eager cache refresh)."""
    eye = jnp.eye(chol_rows.shape[-1], dtype=chol_rows.dtype)
    return jax.vmap(
        lambda c: jax.scipy.linalg.cho_solve((c, True), eye)
    )(chol_rows)


@partial(jax.jit, static_argnames=("mesh",))
def _sh_binv(chol, mesh):
    sh, rep = _bank_axis_specs(mesh)
    return jax.shard_map(_binv_rows, mesh=mesh, in_specs=(sh,),
                         out_specs=sh, check_vma=False)(chol)


@partial(jax.jit, static_argnames=("mesh", "backend", "block_rows"))
def _sh_fit(Xb, yb, maskb, spec, idx, aux, mesh, backend, block_rows):
    """Batched fit, slots sharded over 'bank' and (optionally) rows over
    'data': per-shard moments through the backend registry, one psum over
    the data axes (fit-only — O(M^2) per slot, independent of N), then the
    shared solve epilogue replicated per data shard."""
    bk = fagp.get_backend(backend)
    moments = bk.bank_moments or bank_mod._fallback_bank_moments(bk)
    exp = get_expansion(spec.expansion)
    data_axes = tuple(a for a in mesh.axis_names if a != "bank")
    omega_t = shardspec.omega_args(spec)
    sh, rep = _bank_axis_specs(mesh)
    row_sh = P("bank", *data_axes) if data_axes else sh

    def body(X_l, y_l, m_l, idx_, eps, rho, noise, aux_l, *omega_l):
        s_loc = shardspec.spec_local(
            spec, eps, rho, omega_l[0] if omega_l else None
        )
        G, b = moments(X_l, y_l, s_loc, idx_, aux_l, block_rows, m_l)
        if data_axes:
            G = jax.lax.psum(G, data_axes)
            b = jax.lax.psum(b, data_axes)
        loglam = exp.log_eigenvalues(idx_, s_loc)
        return _bank_solve(G, b, loglam, noise**2) + (b,)

    aux_specs = jax.tree_util.tree_map(lambda _: rep, aux)
    in_specs = (row_sh, row_sh, row_sh, rep, rep, rep, rep, aux_specs) + \
        (rep,) * len(omega_t)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(sh,) * 5, check_vma=False)(
        Xb, yb, maskb, idx, spec.eps, spec.rho,
        jnp.asarray(spec.noise, jnp.float32), aux, *omega_t,
    )


@partial(jax.jit, static_argnames=("mesh",))
def _sh_mean_var(binv, u_s, sqrtlam_s, lslots, Xq, spec, idx, mesh):
    """Mixed-tenant serving on per-shard packed queries: featurize and
    gather the posterior entirely shard-locally — zero collectives."""
    exp = get_expansion(spec.expansion)
    omega_t = shardspec.omega_args(spec)
    sh, rep = _bank_axis_specs(mesh)

    def body(binv_l, u_l, sq_l, sl_l, Xq_l, idx_, eps, rho, *omega_l):
        s_loc = shardspec.spec_local(
            spec, eps, rho, omega_l[0] if omega_l else None
        )
        Phis = exp.features(Xq_l, idx_, s_loc)
        return fagp._bank_gathered_posterior(binv_l, u_l, sq_l, sl_l, Phis)

    in_specs = (sh, sh, sh, sh, sh, rep, rep, rep) + (rep,) * len(omega_t)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(sh, sh), check_vma=False)(
        binv, u_s, sqrtlam_s, lslots, Xq, idx, spec.eps, spec.rho, *omega_t,
    )


@partial(jax.jit, static_argnames=("mesh",))
def _sh_update_scatter(chol_s, u_s, b_s, sqrtlam_s, binv, lslots, Xg, yg,
                       maskg, spec, idx, mesh):
    """Per-shard rank-k update scatter + eager B^{-1} row refresh.  The
    body is the resident ``_bank_update_scatter_impl`` on local leaves —
    fully-masked per-shard padding groups are exact identity writes."""
    exp = get_expansion(spec.expansion)
    omega_t = shardspec.omega_args(spec)
    sh, rep = _bank_axis_specs(mesh)

    def body(chol_l, u_l, b_l, sq_l, binv_l, sl_l, Xg_l, yg_l, mg_l,
             idx_, eps, rho, noise, *omega_l):
        s_loc = shardspec.spec_local(
            spec, eps, rho, omega_l[0] if omega_l else None
        )
        G, k, p = Xg_l.shape
        Phi_g = exp.features(Xg_l.reshape(G * k, p), idx_, s_loc)
        Phi_g = Phi_g.reshape(G, k, -1)
        noise_g = jnp.broadcast_to(noise, (G,))
        chol_l, u_l, b_l = bank_mod._bank_update_scatter_impl(
            chol_l, u_l, b_l, sq_l, noise_g, sl_l, Phi_g, yg_l, mg_l,
        )
        binv_l = binv_l.at[sl_l].set(_binv_rows(chol_l[sl_l]))
        return chol_l, u_l, b_l, binv_l

    in_specs = (sh, sh, sh, sh, sh, sh, sh, sh, sh, rep, rep, rep, rep) + \
        (rep,) * len(omega_t)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(sh,) * 4, check_vma=False)(
        chol_s, u_s, b_s, sqrtlam_s, binv, lslots, Xg, yg, maskg,
        idx, spec.eps, spec.rho, jnp.asarray(spec.noise, jnp.float32),
        *omega_t,
    )


@partial(jax.jit, static_argnames=("mesh",))
def _sh_downdate_scatter(chol_s, u_s, b_s, sqrtlam_s, binv, lslots, Xg, yg,
                         maskg, spec, idx, mesh):
    """Per-shard rank-k downdate mirror (rides the resident
    ``_bank_downdate_scatter``); returns the per-group ok flags in the
    packed per-shard order."""
    exp = get_expansion(spec.expansion)
    omega_t = shardspec.omega_args(spec)
    sh, rep = _bank_axis_specs(mesh)

    def body(chol_l, u_l, b_l, sq_l, binv_l, sl_l, Xg_l, yg_l, mg_l,
             idx_, eps, rho, noise, *omega_l):
        s_loc = shardspec.spec_local(
            spec, eps, rho, omega_l[0] if omega_l else None
        )
        G, k, p = Xg_l.shape
        Phi_g = exp.features(Xg_l.reshape(G * k, p), idx_, s_loc)
        Phi_g = Phi_g.reshape(G, k, -1)
        noise_g = jnp.broadcast_to(noise, (G,))
        chol_l, u_l, b_l, ok = bank_mod._bank_downdate_scatter(
            chol_l, u_l, b_l, sq_l, noise_g, sl_l, Phi_g, yg_l, mg_l,
        )
        binv_l = binv_l.at[sl_l].set(_binv_rows(chol_l[sl_l]))
        return chol_l, u_l, b_l, binv_l, ok

    in_specs = (sh, sh, sh, sh, sh, sh, sh, sh, sh, rep, rep, rep, rep) + \
        (rep,) * len(omega_t)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(sh,) * 5, check_vma=False)(
        chol_s, u_s, b_s, sqrtlam_s, binv, lslots, Xg, yg, maskg,
        idx, spec.eps, spec.rho, jnp.asarray(spec.noise, jnp.float32),
        *omega_t,
    )


@partial(jax.jit, static_argnames=("mesh",))
def _sh_refit_scatter(chol_s, u_s, b_s, lam_s, sqrtlam_s, binv, lslots,
                      Xg, yg, maskg, spec, idx, mesh):
    """Per-shard masked window refit (rides the resident
    ``_bank_refit_scatter`` under the shared hyperparameters)."""
    omega_t = shardspec.omega_args(spec)
    sh, rep = _bank_axis_specs(mesh)

    def body(chol_l, u_l, b_l, lam_l, sq_l, binv_l, sl_l, Xg_l, yg_l, mg_l,
             idx_, eps, rho, noise, *omega_l):
        s_loc = shardspec.spec_local(
            spec, eps, rho, omega_l[0] if omega_l else None
        )
        G = Xg_l.shape[0]
        eps_g = jnp.broadcast_to(eps, (G,) + eps.shape)
        rho_g = jnp.broadcast_to(rho, (G,) + rho.shape)
        noise_g = jnp.broadcast_to(noise, (G,))
        chol_l, u_l, b_l, lam_l, sq_l = bank_mod._bank_refit_scatter(
            chol_l, u_l, b_l, lam_l, sq_l, sl_l, Xg_l, yg_l, mg_l,
            eps_g, rho_g, noise_g,
            dataclasses.replace(s_loc, noise=noise), idx_,
        )
        binv_l = binv_l.at[sl_l].set(_binv_rows(chol_l[sl_l]))
        return chol_l, u_l, b_l, lam_l, sq_l, binv_l

    in_specs = (sh,) * 10 + (rep, rep, rep, rep) + (rep,) * len(omega_t)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(sh,) * 6, check_vma=False)(
        chol_s, u_s, b_s, lam_s, sqrtlam_s, binv, lslots, Xg, yg, maskg,
        idx, spec.eps, spec.rho, jnp.asarray(spec.noise, jnp.float32),
        *omega_t,
    )


@partial(jax.jit, static_argnames=("mesh",))
def _sh_write_slot(chol_s, u_s, b_s, lam_s, sqrtlam_s, binv, gslot,
                   chol, u, b, lam, sqrtlam, mesh):
    """Write one tenant's leaves at a *traced* GLOBAL slot: the owning
    shard applies the write, every other shard rewrites its own row
    verbatim — insert/evict/rebalance of any slot on any shard hit this
    one executable.  The written slot's B^{-1} row refreshes in place."""
    sh, rep = _bank_axis_specs(mesh)

    def body(chol_l, u_l, b_l, lam_l, sq_l, binv_l, gs, *new):
        C_l = chol_l.shape[0]
        me = jax.lax.axis_index("bank")
        loc = gs % C_l
        mine = (gs // C_l) == me

        def wr(leaf, val):
            row = jax.lax.dynamic_index_in_dim(leaf, loc, 0, keepdims=False)
            upd = jnp.where(mine, val, row)
            return jax.lax.dynamic_update_index_in_dim(leaf, upd, loc, 0)

        chol_l = wr(chol_l, new[0])
        u_l = wr(u_l, new[1])
        b_l = wr(b_l, new[2])
        lam_l = wr(lam_l, new[3])
        sq_l = wr(sq_l, new[4])
        row = jax.lax.dynamic_index_in_dim(chol_l, loc, 0, keepdims=False)
        binv_l = wr(binv_l, _binv_rows(row[None])[0])
        return chol_l, u_l, b_l, lam_l, sq_l, binv_l

    in_specs = (sh,) * 6 + (rep,) * 6
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(sh,) * 6, check_vma=False)(
        chol_s, u_s, b_s, lam_s, sqrtlam_s, binv, gslot,
        chol, u, b, lam, sqrtlam,
    )


@jax.jit
def _sh_read_slot(chol_s, u_s, b_s, lam_s, sqrtlam_s, gslot):
    """Gather one slot's leaves at a *traced* global index — the unstack
    path (``state``/``rebalance``) stays zero-recompile across slots and
    shards.  Cross-shard by nature; never on the serving hot path."""
    rd = lambda a: jax.lax.dynamic_index_in_dim(a, gslot, 0, keepdims=False)
    return rd(chol_s), rd(u_s), rd(b_s), rd(lam_s), rd(sqrtlam_s)


# ---------------------------------------------------------------------------
# the sharded bank
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedGPBank:
    """A :class:`GPBank` whose capacity axis is sharded over a mesh's
    'bank' axis (see module doc).  Public surface mirrors ``GPBank`` —
    the router, engine and tiered lifecycle drive either interchangeably.

    stack:  stacked FAGPState, leaves device-sharded P('bank').
    mesh:   the device mesh (first axis 'bank'; extra axes are the v2
            data axes, used by fit only).
    binv:   eagerly-maintained per-slot B^{-1} cache, sharded alongside.
    active: (capacity,) host bool mask.
    slots:  tenant -> GLOBAL slot (shard = slot // shard_capacity).
    hypers: always None — sharded banks are homogeneous (see module doc).
    """

    stack: FAGPState
    mesh: Any
    binv: jax.Array
    active: np.ndarray
    slots: Mapping[Hashable, int]
    hypers: Optional[SEKernelParams] = None

    def __post_init__(self):
        if self.hypers is not None:
            raise ValueError(
                "ShardedGPBank is homogeneous-only: per-slot hyperparameter"
                " overlays (GPBank.optimize) have no shard-local serving "
                "path yet — convert with to_bank() first"
            )

    # -- constructors -------------------------------------------------------

    @classmethod
    def create(cls, spec: GPSpec, capacity: int, mesh) -> "ShardedGPBank":
        """An empty sharded bank: every slot holds the prior state."""
        res = GPBank.create(spec, cls._check_capacity(capacity, mesh))
        return cls.from_bank(res, mesh)

    @classmethod
    def fit(
        cls,
        Xb: jax.Array,
        yb: jax.Array,
        spec: GPSpec,
        mesh,
        *,
        mask: Optional[jax.Array] = None,
        tenant_ids: Optional[Sequence[Hashable]] = None,
        capacity: Optional[int] = None,
    ) -> "ShardedGPBank":
        """Fit B independent GPs in one sharded batched pass (same data
        contract as :meth:`GPBank.fit`).  Tenants place round-robin across
        shards (tenant i -> shard i mod S), packed from each shard's lowest
        local slot; reserved capacity pads with masked rows that factorize
        to exactly the prior leaves."""
        Xb = np.asarray(Xb, np.float32)
        yb = np.asarray(yb, np.float32)
        if Xb.ndim != 3 or yb.ndim != 2 or yb.shape != Xb.shape[:2]:
            raise ValueError(
                f"ShardedGPBank.fit wants Xb (B, N, p) and yb (B, N); got "
                f"{Xb.shape} and {yb.shape}"
            )
        B, N, p = Xb.shape
        S = int(mesh.shape["bank"])
        cap = (-(-B // S) * S) if capacity is None else int(capacity)
        cap = cls._check_capacity(cap, mesh)
        if cap < B:
            raise ValueError(f"capacity {cap} < number of tenants {B}")
        C_l = cap // S
        if tenant_ids is None:
            tenant_ids = range(B)
        tenant_ids = list(tenant_ids)
        if len(tenant_ids) != B or len(set(tenant_ids)) != B:
            raise ValueError(
                f"tenant_ids must be {B} distinct ids, got {tenant_ids!r}"
            )
        spec = _bank_spec(spec)
        fagp._check_p(spec, p)
        if mask is None:
            mask = np.ones((B, N), np.float32)
        else:
            mask = np.asarray(mask, np.float32)
            if mask.shape != (B, N):
                raise ValueError(
                    f"mask must be (B, N) = {(B, N)}, got {mask.shape}"
                )
        # round-robin placement: tenant i -> global slot (i%S)*C_l + i//S
        gslots = (np.arange(B) % S) * C_l + np.arange(B) // S
        # pad the row axis to the data-axis quantum (2-D mesh fits only)
        dsize = int(np.prod([
            mesh.shape[a] for a in mesh.axis_names if a != "bank"
        ]))
        N_pad = -(-N // dsize) * dsize
        Xf = np.zeros((cap, N_pad, p), np.float32)
        yf = np.zeros((cap, N_pad), np.float32)
        mf = np.zeros((cap, N_pad), np.float32)
        Xf[gslots, :N] = Xb
        yf[gslots, :N] = yb
        mf[gslots, :N] = mask
        backend = fagp._check_backend_support(spec)
        idx_np = spec.indices(p)
        idx = jnp.asarray(idx_np)
        aux = backend.prepare(idx_np, spec)
        block_rows = min(spec.block_rows, max(1, N))
        data_axes = tuple(a for a in mesh.axis_names if a != "bank")
        row_shd = NamedSharding(
            mesh, P("bank", *data_axes) if data_axes else P("bank")
        )
        put = lambda a: jax.device_put(a, row_shd)
        lam, sqrtlam, chol, u, b = _sh_fit(
            put(Xf), put(yf), put(mf), spec, idx, aux, mesh,
            spec.backend, block_rows,
        )
        stack = FAGPState(
            idx=idx, lam=lam, sqrtlam=sqrtlam, chol=chol, u=u,
            params=spec.params, Phi=None, y=None, b=b, spec=spec,
        )
        active = np.zeros(cap, bool)
        active[gslots] = True
        return cls(
            stack=stack, mesh=mesh, binv=_sh_binv(chol, mesh),
            active=active,
            slots={t: int(s) for t, s in zip(tenant_ids, gslots)},
        )

    @classmethod
    def from_bank(cls, bank: GPBank, mesh, *,
                  pad_capacity: bool = False) -> "ShardedGPBank":
        """Shard a resident bank in place: slots keep their global ids
        (shard = slot // shard_capacity).  ``pad_capacity`` rounds the
        capacity up to a shard multiple with prior slots instead of
        raising."""
        if bank.hypers is not None:
            raise ValueError(
                "cannot shard a heterogeneous bank (per-slot overlays have "
                "no shard-local serving path yet)"
            )
        S = int(mesh.shape["bank"])
        cap = bank.capacity
        if cap % S and pad_capacity:
            bigger = GPBank.create(bank.spec, -(-cap // S) * S)
            leaves = {
                f: jnp.concatenate([
                    getattr(bank.stack, f), getattr(bigger.stack, f)[cap:],
                ])
                for f in ("lam", "sqrtlam", "chol", "u", "b")
            }
            stack = dataclasses.replace(bank.stack, **leaves)
            active = np.zeros(bigger.capacity, bool)
            active[:cap] = bank.active
            bank = GPBank(stack=stack, active=active, slots=dict(bank.slots))
            cap = bank.capacity
        cap = cls._check_capacity(cap, mesh)
        shd = _leaf_sharding(mesh)
        leaves = {
            f: jax.device_put(getattr(bank.stack, f), shd)
            for f in ("lam", "sqrtlam", "chol", "u", "b")
        }
        stack = dataclasses.replace(bank.stack, **leaves)
        return cls(
            stack=stack, mesh=mesh, binv=_sh_binv(stack.chol, mesh),
            active=bank.active.copy(), slots=dict(bank.slots),
        )

    def to_bank(self) -> GPBank:
        """Gather the shards back into a single-device resident bank."""
        leaves = {
            f: jnp.asarray(np.asarray(getattr(self.stack, f)))
            for f in ("lam", "sqrtlam", "chol", "u", "b")
        }
        stack = dataclasses.replace(self.stack, **leaves)
        return GPBank(stack=stack, active=self.active.copy(),
                      slots=dict(self.slots))

    @staticmethod
    def _check_capacity(capacity: int, mesh) -> int:
        S = int(mesh.shape.get("bank", 0))
        if S < 1:
            raise ValueError(
                f"mesh needs a 'bank' axis; got {mesh.axis_names!r}"
            )
        if capacity < 1 or capacity % S:
            raise ValueError(
                f"capacity must be a positive multiple of the bank axis "
                f"size {S}, got {capacity}"
            )
        return int(capacity)

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
    def n_shards(self) -> int:
        return int(self.mesh.shape["bank"])

    @property
    def shard_capacity(self) -> int:
        return self.capacity // self.n_shards

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

    def shard_of(self, tenant: Hashable) -> int:
        """Which shard owns this tenant's slot."""
        return self.slot_of(tenant) // self.shard_capacity

    def shard_occupancy(self) -> np.ndarray:
        """(S,) active-tenant count per shard (host-side, no sync)."""
        return self.active.reshape(self.n_shards, -1).sum(axis=1)

    def state(self, tenant: Hashable) -> FAGPState:
        """The tenant's session, unstacked (traced-slot gather — paging any
        slot on any shard out is one executable)."""
        s = self.slot_of(tenant)
        st = self.stack
        chol, u, b, lam, sqrtlam = _sh_read_slot(
            st.chol, st.u, st.b, st.lam, st.sqrtlam, jnp.int32(s)
        )
        return dataclasses.replace(
            st, lam=lam, sqrtlam=sqrtlam, chol=chol, u=u, b=b
        )

    def states(self) -> dict:
        return {t: self.state(t) for t in self.slots}

    def _stacked_hypers(self) -> SEKernelParams:
        sp = self.spec
        C = self.capacity
        return SEKernelParams(
            eps=jnp.broadcast_to(sp.eps, (C,) + sp.eps.shape),
            rho=jnp.broadcast_to(sp.rho, (C,) + sp.rho.shape),
            noise=jnp.broadcast_to(jnp.asarray(sp.noise, jnp.float32), (C,)),
        )

    @property
    def _binv(self) -> jax.Array:
        """The serving cache — eager in a sharded bank (every mutating
        executable refreshes its touched rows shard-locally)."""
        return self.binv

    def _slots_np(self, tenant_ids) -> np.ndarray:
        if isinstance(tenant_ids, (str, bytes)) or not hasattr(
            tenant_ids, "__iter__"
        ):
            raise TypeError(
                "tenant_ids must be a sequence of tenant ids, one per row "
                f"(got a scalar {tenant_ids!r}); for a single-tenant batch "
                "pass [tenant] * len(Xq)"
            )
        return np.fromiter(
            (self.slot_of(t) for t in tenant_ids), np.int64,
        )

    _slots_for = _slots_np

    @staticmethod
    def result_ready(*arrays) -> bool:
        """See :meth:`GPBank.result_ready` (one definition)."""
        return GPBank.result_ready(*arrays)

    # -- the batched pipeline ----------------------------------------------

    def _packed_mean_var(self, gslots: np.ndarray, Xq: np.ndarray):
        """Serving core on global slots: per-shard pack, one shard-local
        executable, results in PACKED order plus the position map — the
        engine unpacks host-side at harvest (no device reorder on the hot
        path)."""
        S, C_l = self.n_shards, self.shard_capacity
        lslots, pos, Qs = _group_rows(gslots, C_l, S)
        Xp = np.zeros((S * Qs, Xq.shape[1]), np.float32)
        Xp[pos] = Xq
        shd = _leaf_sharding(self.mesh)
        mu, var = _sh_mean_var(
            self.binv, self.stack.u, self.stack.sqrtlam,
            jax.device_put(lslots, shd), jax.device_put(Xp, shd),
            self.spec, self.stack.idx, self.mesh,
        )
        return mu, var, pos

    def mean_var(self, tenant_ids, Xq: jax.Array):
        """Posterior mean and marginal variance for a mixed-tenant query
        batch (same contract as :meth:`GPBank.mean_var`); one shard-local
        compiled call plus a gather back to row order."""
        Xq = np.asarray(Xq, np.float32)
        gslots = self._slots_np(tenant_ids)
        if gslots.shape[0] != Xq.shape[0]:
            raise ValueError(
                f"one tenant id per query row: got {gslots.shape[0]} ids "
                f"for {Xq.shape[0]} rows"
            )
        mu, var, pos = self._packed_mean_var(gslots, Xq)
        unpack = jnp.asarray(pos, jnp.int32)
        return mu[unpack], var[unpack]

    # -- ingest / forgetting ------------------------------------------------

    def update(self, tenant_ids, Xk, yk, mask=None) -> "ShardedGPBank":
        """Batched rank-k ingest (same contract as :meth:`GPBank.update`)."""
        Xk = np.asarray(Xk, np.float32)
        yk = np.asarray(yk, np.float32)
        if Xk.ndim != 3 or yk.shape != Xk.shape[:2]:
            raise ValueError(
                f"update wants Xk (G, k, p) and yk (G, k); got "
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
        return self._update_at_slots(self._slots_np(ids), Xk, yk, mask)

    def _group_scatter_args(self, slots, Xg, yg, mask):
        """Shared host-side prep for the scatter ops: per-shard grouping
        with pow2 rung padding; padding groups fully masked on distinct
        untargeted slots."""
        Xg = np.asarray(Xg, np.float32)
        yg = np.asarray(yg, np.float32)
        G, k, p = Xg.shape
        fagp._check_p(self.spec, p)
        if mask is None:
            mask = np.ones((G, k), np.float32)
        else:
            mask = np.asarray(mask, np.float32)
            if mask.shape != (G, k):
                raise ValueError(
                    f"mask must be (G, k) = {(G, k)}, got {mask.shape}"
                )
        gslots = np.asarray(slots, np.int64).reshape(-1)
        S, C_l = self.n_shards, self.shard_capacity
        lslots, pos, Qs = _group_slots(gslots, C_l, S)
        Xp = np.zeros((S * Qs, k, p), np.float32)
        yp = np.zeros((S * Qs, k), np.float32)
        mp = np.zeros((S * Qs, k), np.float32)
        Xp[pos] = Xg
        yp[pos] = yg
        mp[pos] = mask
        shd = _leaf_sharding(self.mesh)
        put = lambda a: jax.device_put(a, shd)
        return put(lslots), put(Xp), put(yp), put(mp), pos

    def _update_at_slots(self, slots, Xk, yk, mask=None,
                         donate: bool = False) -> "ShardedGPBank":
        """Slot-addressed core of :meth:`update` (global slots; the
        router's fixed-shape entry).  ``donate`` is accepted for router
        compatibility and ignored — the sharded scatter carries the eager
        B^{-1} refresh in the same executable, and donation is a no-op on
        the host-platform devices this mode targets."""
        lslots, Xp, yp, mp, _ = self._group_scatter_args(slots, Xk, yk, mask)
        st = self.stack
        chol, u, b, binv = _sh_update_scatter(
            st.chol, st.u, st.b, st.sqrtlam, self.binv, lslots, Xp, yp, mp,
            self.spec, st.idx, self.mesh,
        )
        stack = dataclasses.replace(st, chol=chol, u=u, b=b)
        return dataclasses.replace(self, stack=stack, binv=binv)

    def downdate(self, tenant_ids, Xk, yk, mask=None):
        """Batched rank-k forget (same contract as
        :meth:`GPBank.downdate`): returns ``(bank, ok)``."""
        Xk = np.asarray(Xk, np.float32)
        yk = np.asarray(yk, np.float32)
        if Xk.ndim != 3 or yk.shape != Xk.shape[:2]:
            raise ValueError(
                f"downdate wants Xk (G, k, p) and yk (G, k); got "
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
        return self._downdate_at_slots(self._slots_np(ids), Xk, yk, mask)

    def _downdate_at_slots(self, slots, Xk, yk, mask=None):
        lslots, Xp, yp, mp, pos = self._group_scatter_args(
            slots, Xk, yk, mask
        )
        st = self.stack
        chol, u, b, binv, ok = _sh_downdate_scatter(
            st.chol, st.u, st.b, st.sqrtlam, self.binv, lslots, Xp, yp, mp,
            self.spec, st.idx, self.mesh,
        )
        stack = dataclasses.replace(st, chol=chol, u=u, b=b)
        new = dataclasses.replace(self, stack=stack, binv=binv)
        return new, np.asarray(ok)[pos]

    def refit_window(self, tenant_ids, Xw, yw, mask=None) -> "ShardedGPBank":
        """Window refit fallback (same contract as
        :meth:`GPBank.refit_window`)."""
        Xw = np.asarray(Xw, np.float32)
        yw = np.asarray(yw, np.float32)
        if Xw.ndim != 3 or yw.shape != Xw.shape[:2]:
            raise ValueError(
                f"refit_window wants Xw (G, W, p) and yw (G, W); got "
                f"{Xw.shape} and {yw.shape}"
            )
        ids = list(tenant_ids)
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate tenant in one refit batch ({ids!r})")
        if len(ids) != Xw.shape[0]:
            raise ValueError(
                f"one tenant id per refit group: got {len(ids)} ids for "
                f"{Xw.shape[0]} groups"
            )
        return self._refit_at_slots(self._slots_np(ids), Xw, yw, mask)

    def _refit_at_slots(self, slots, Xw, yw, mask=None) -> "ShardedGPBank":
        lslots, Xp, yp, mp, _ = self._group_scatter_args(slots, Xw, yw, mask)
        W = Xp.shape[1]
        spec_r = self.spec.replace(
            block_rows=min(self.spec.block_rows, max(1, W))
        )
        st = self.stack
        chol, u, b, lam, sqrtlam, binv = _sh_refit_scatter(
            st.chol, st.u, st.b, st.lam, st.sqrtlam, self.binv, lslots,
            Xp, yp, mp, spec_r, st.idx, self.mesh,
        )
        stack = dataclasses.replace(st, chol=chol, u=u, b=b, lam=lam,
                                    sqrtlam=sqrtlam)
        return dataclasses.replace(self, stack=stack, binv=binv)

    # -- membership churn (traced slot: zero recompiles per shard) ----------

    def _free_slot_on(self, shard: int) -> Optional[int]:
        C_l = self.shard_capacity
        free = np.flatnonzero(~self.active[shard * C_l:(shard + 1) * C_l])
        return None if free.size == 0 else shard * C_l + int(free[0])

    def _placement_shard(self) -> int:
        """Least-loaded shard with a free slot (ties -> lowest id) — the
        placement policy; ``TieredBank`` cold-restores inherit it through
        :meth:`insert`."""
        occ = self.shard_occupancy()
        order = np.lexsort((np.arange(self.n_shards), occ))
        C_l = self.shard_capacity
        for s in order:
            if occ[s] < C_l:
                return int(s)
        raise ValueError(
            f"bank is full ({self.capacity} slots); evict a tenant or "
            f"rebuild with a larger capacity"
        )

    def _write(self, gslot: int, leaves) -> FAGPState:
        st = self.stack
        chol, u, b, lam, sqrtlam, binv = _sh_write_slot(
            st.chol, st.u, st.b, st.lam, st.sqrtlam, self.binv,
            jnp.int32(gslot), leaves["chol"], leaves["u"], leaves["b"],
            leaves["lam"], leaves["sqrtlam"], self.mesh,
        )
        stack = dataclasses.replace(st, chol=chol, u=u, b=b, lam=lam,
                                    sqrtlam=sqrtlam)
        return stack, binv

    def insert(self, tenant: Hashable, source) -> "ShardedGPBank":
        """Add a tenant on the least-loaded shard (same source contract as
        :meth:`GPBank.insert`; one traced-slot executable regardless of
        slot or shard)."""
        if tenant in self.slots:
            raise ValueError(f"tenant {tenant!r} already in the bank")
        shard = self._placement_shard()
        slot = self._free_slot_on(shard)
        if isinstance(source, tuple):
            X, y = source
            st = fagp.fit(jnp.asarray(X), jnp.asarray(y), self.spec)
        else:
            st = source.state if isinstance(source, GP) else source
        _check_bankable(st, self.spec, f"insert({tenant!r})")
        stack, binv = self._write(slot, {
            "chol": st.chol, "u": st.u, "b": st.b, "lam": st.lam,
            "sqrtlam": st.sqrtlam,
        })
        active = self.active.copy()
        active[slot] = True
        slots = dict(self.slots)
        slots[tenant] = slot
        return dataclasses.replace(self, stack=stack, binv=binv,
                                   active=active, slots=slots)

    def evict(self, tenant: Hashable) -> "ShardedGPBank":
        """Remove a tenant; its slot resets to the prior state — same
        executable as :meth:`insert`."""
        slot = self.slot_of(tenant)
        loglam = get_expansion(self.spec.expansion).log_eigenvalues(
            self.stack.idx, self.spec
        )
        prior = _prior_leaves(loglam, 1)
        stack, binv = self._write(slot, {f: prior[f][0] for f in prior})
        active = self.active.copy()
        active[slot] = False
        slots = {t: s for t, s in self.slots.items() if t != tenant}
        return dataclasses.replace(self, stack=stack, binv=binv,
                                   active=active, slots=slots)

    # -- cross-shard rebalancing -------------------------------------------

    def rebalance(self, max_moves: Optional[int] = None):
        """Move tenants from the fullest shards to the emptiest until the
        occupancy spread is <= 1 (or ``max_moves`` is hit).  Each move is
        one traced-slot gather plus two traced-slot writes — zero new
        executables however the fleet churned.  Deterministic: donor is
        the fullest shard (ties -> lowest id), the migrant its
        highest-numbered occupied local slot.

        Returns ``(bank, moves)``."""
        bank = self
        moves = 0
        C_l = self.shard_capacity
        while max_moves is None or moves < max_moves:
            occ = bank.shard_occupancy()
            donor = int(np.lexsort((np.arange(len(occ)), -occ))[0])
            recv = int(np.lexsort((np.arange(len(occ)), occ))[0])
            if occ[donor] - occ[recv] <= 1:
                break
            local = np.flatnonzero(bank.active[donor * C_l:(donor + 1) * C_l])
            src = donor * C_l + int(local[-1])
            tenant = next(t for t, s in bank.slots.items() if s == src)
            dst = bank._free_slot_on(recv)
            st = bank.stack
            chol, u, b, lam, sqrtlam = _sh_read_slot(
                st.chol, st.u, st.b, st.lam, st.sqrtlam, jnp.int32(src)
            )
            stack, binv = bank._write(dst, {
                "chol": chol, "u": u, "b": b, "lam": lam,
                "sqrtlam": sqrtlam,
            })
            bank = dataclasses.replace(bank, stack=stack, binv=binv)
            loglam = get_expansion(bank.spec.expansion).log_eigenvalues(
                bank.stack.idx, bank.spec
            )
            prior = _prior_leaves(loglam, 1)
            stack, binv = bank._write(src, {f: prior[f][0] for f in prior})
            active = bank.active.copy()
            active[src] = False
            active[dst] = True
            slots = dict(bank.slots)
            slots[tenant] = dst
            bank = dataclasses.replace(bank, stack=stack, binv=binv,
                                       active=active, slots=slots)
            moves += 1
        return bank, moves

    # -- unsupported resident-only surface ---------------------------------

    def optimize(self, *a, **k):
        raise NotImplementedError(
            "fleet hyperparameter optimization produces a heterogeneous "
            "bank, which has no shard-local serving path yet — "
            "to_bank().optimize(...) and re-shard after"
        )
