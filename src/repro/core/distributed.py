"""Distributed FAGP over the (pod, data, model) production mesh.

The paper parallelizes the FAGP posterior on ONE GPU with cuBLAS GEMMs.
At pod scale the data no longer fits one device, so the algorithm becomes:

  * X, y row-sharded over (pod, data) — each chip owns N/dp rows;
  * Phi built block-streamed (never materialized for the full N);
  * the two sufficient statistics G = Phi^T Phi (M x M) and b = Phi^T y (M)
    are partial-summed locally and combined by ONE all-reduce each —
    communication volume O(M^2), independent of N (the communication-
    optimal schedule for tall-skinny Gram matrices);
  * G/B kept row-sharded over 'model'; the M x M Cholesky solve runs on the
    gathered matrix (M <= ~16k => <1 GB f32, affordable once per fit);
  * prediction: Phi* row-sharded over (pod, data), mean/variance local per
    shard — embarrassingly parallel, zero collectives after the broadcast
    of (chol, u).

Everything is pjit + sharding constraints: the all-reduces appear in the
lowered HLO (verified by the dry-run's collective parse).

The schedules are expansion-generic: the feature map and log weights come
from the spec's registered :class:`~repro.core.expansions.KernelExpansion`,
so an RFF fit shards exactly like a Hermite fit (the RFF spectral draws
``spec.omega`` are replicated alongside eps/rho — they are hyperparameters,
not data).

API (same self-describing session contract as ``core.fagp``):

    state = fit_distributed(X, y, spec, mesh)       # a normal FAGPState
    mu, var = predict_distributed(Xs, state, mesh)  # spec baked in

The returned state is interchangeable with a single-device fit — it feeds
``predict_mean_var``, ``fit_update`` and the ``GP`` facade directly.  The
split ``fit_distributed(X, y, params, cfg, mesh) -> (u, chol, sqrtlam)``
form was deprecated for two releases and now raises TypeError.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.parallel import hints
from . import shardspec
from .expansions import get_expansion
from .fagp import (
    FAGPState,
    GPSpec,
    _assemble_scaled_system,
    _removed,
    _solve_mean_weights,
    get_backend,
)

__all__ = ["fit_distributed", "predict_distributed", "lower_fit", "lower_predict"]


# Shard-local spec rebuild + mesh probes live in core.shardspec so the
# bank-axis sharding (bank.sharded) shares one copy with the v2 schedules.
_spec_local = shardspec.spec_local
_omega_args = shardspec.omega_args


@partial(jax.jit, static_argnames=("nblk", "n_valid"))
def _fit_fn(X, y, spec: GPSpec, idx, nblk: int, n_valid: int | None = None):
    exp = get_expansion(spec.expansion)
    N = X.shape[0]
    M = idx.shape[0]
    sig2 = spec.noise**2
    loglam = exp.log_eigenvalues(idx, spec)

    block = N // nblk
    Xb = hints.constrain(X.reshape(nblk, block, -1), (None, "dp", None))
    yb = hints.constrain(y.reshape(nblk, block), (None, "dp"))

    def step(carry, inp):
        G, b = carry
        i, Xi, yi = inp
        Xi = hints.constrain(Xi, ("dp", None))
        Phi_i = exp.features(Xi, idx, spec)              # rows sharded over dp
        if n_valid is not None and n_valid < N:          # mask padded rows
            mask = ((i * block + jnp.arange(block)) < n_valid).astype(Phi_i.dtype)
            Phi_i = Phi_i * mask[:, None]
            yi = yi * mask
        G = G + hints.constrain(Phi_i.T @ Phi_i, ("model", None))
        b = b + Phi_i.T @ yi
        return (G, b), None

    G0 = hints.constrain(jnp.zeros((M, M), X.dtype), ("model", None))
    (G, b), _ = jax.lax.scan(
        step, (G0, jnp.zeros((M,), X.dtype)), (jnp.arange(nblk), Xb, yb)
    )

    B, sqrtlam = _assemble_scaled_system(G, loglam, sig2)
    B = hints.constrain(B, ("model", None))
    chol = jnp.linalg.cholesky(B)
    u = _solve_mean_weights(chol, sqrtlam, b, sig2)
    return u, chol, sqrtlam, b


@jax.jit
def _predict_fn(Xs, u, chol, sqrtlam, spec: GPSpec, idx):
    exp = get_expansion(spec.expansion)
    Xs = hints.constrain(Xs, ("dp", None))
    Phis = exp.features(Xs, idx, spec)                   # (N*, M) rows over dp
    mu = Phis @ u
    PhisD = Phis * sqrtlam[None, :]
    V = jax.scipy.linalg.solve_triangular(chol, PhisD.T, lower=True)
    var = jnp.sum(V * V, axis=0)
    return hints.constrain(mu, ("dp",)), hints.constrain(var, ("dp",))


def _dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in hints.dp_axes(mesh)]))


def _pick_nblk(N: int, M: int, dp: int = 1) -> tuple[int, int]:
    """(nblk, N_padded): row blocks ~100 MB f32 of Phi per device, with
    N padded so blocks exist and every block divides the dp axis."""
    target_rows = max(dp, int(100e6 / 4 / max(M, 1)) * dp)
    nblk = max(1, N // target_rows)
    nblk = min(nblk, 256)
    quantum = nblk * dp
    N_pad = (N + quantum - 1) // quantum * quantum
    return nblk, N_pad


def _fit_distributed_spec(X, y, spec: GPSpec, mesh) -> FAGPState:
    """The actual distributed fit; returns a self-describing FAGPState
    (Phi/y not stored — they are sharded training data, not serving state)."""
    N, p = X.shape
    idx_np = spec.indices(p)
    idx = jnp.asarray(idx_np)
    if spec.backend != "jnp":
        n_chips = _n_chips(mesh)
        N_pad = (N + n_chips - 1) // n_chips * n_chips
        if N_pad != N:
            X = jnp.pad(X, ((0, N_pad - N), (0, 0)))
            y = jnp.pad(y, (0, N_pad - N))
        aux = get_backend(spec.backend).prepare(idx_np, spec)
        with jax.set_mesh(mesh), hints.activate(mesh):
            f = jax.jit(partial(
                _fit_fn_v2, nblk=16, mesh=mesh,
                n_valid=N if N_pad != N else None,
                backend=spec.backend, aux=aux,
            ))
            u, chol, sqrtlam, b = f(X, y, spec, idx)
    else:
        nblk, N_pad = _pick_nblk(N, idx.shape[0], _dp_size(mesh))
        if N_pad != N:
            X = jnp.pad(X, ((0, N_pad - N), (0, 0)))
            y = jnp.pad(y, (0, N_pad - N))
        with jax.set_mesh(mesh), hints.activate(mesh):
            dp = hints.dp_axes(mesh)
            f = jax.jit(
                partial(_fit_fn, nblk=nblk,
                        n_valid=N if N_pad != N else None),
                in_shardings=(
                    NamedSharding(mesh, P(dp, None)), NamedSharding(mesh, P(dp)),
                    None, None,
                ),
            )
            u, chol, sqrtlam, b = f(X, y, spec, idx)
    loglam = get_expansion(spec.expansion).log_eigenvalues(idx, spec)
    return FAGPState(
        idx=idx, lam=jnp.exp(loglam), sqrtlam=sqrtlam, chol=chol, u=u,
        params=spec.params, Phi=None, y=None, b=b, spec=spec,
    )


def fit_distributed(X, y, spec, *args):
    """Distributed fit returning a self-describing :class:`FAGPState`.

    ``fit_distributed(X, y, spec, mesh)``.  ``spec.backend`` selects the
    per-shard engine via the core.fagp registry: 'jnp' runs the v1 pjit
    schedule, anything else runs the v2 shard_map schedule with that
    backend's streaming moments kernel per shard (e.g. 'pallas' = the
    features and Gram kernels chunk by chunk, O(M^2) live memory — for
    any registered expansion).

    The split ``fit_distributed(X, y, params, cfg, mesh)`` form was removed.
    """
    if not isinstance(spec, GPSpec):
        _removed(
            "fit_distributed(X, y, params, cfg, mesh)",
            "merge them with GPSpec.from_parts(params, cfg) and call "
            "fit_distributed(X, y, spec, mesh), which returns an FAGPState",
        )
    if len(args) != 1:
        raise TypeError("fit_distributed(X, y, spec, mesh): expected mesh")
    return _fit_distributed_spec(X, y, spec, args[0])


def predict_distributed(Xs, state, *args):
    """Shard-local posterior mean/variance over the mesh.

    ``predict_distributed(Xs, state, mesh)`` with the self-describing state
    returned by :func:`fit_distributed` (or a single-device ``fit`` — the
    schedule only needs u/chol/sqrtlam).

    The ``predict_distributed(Xs, (u, chol, sqrtlam), params, cfg, mesh)``
    form was removed.
    """
    if len(args) != 1:
        _removed(
            "predict_distributed(Xs, state_tuple, params, cfg, mesh)",
            "fit with fit_distributed(X, y, spec, mesh) and call "
            "predict_distributed(Xs, state, mesh)",
        )
    mesh = args[0]
    if not isinstance(state, FAGPState) or state.spec is None:
        raise ValueError(
            "predict_distributed(Xs, state, mesh) needs a self-describing "
            "FAGPState (from fit_distributed or fit)"
        )
    spec = state.spec
    u, chol, sqrtlam = state.u, state.chol, state.sqrtlam
    idx = state.idx
    N = Xs.shape[0]
    dpn = _dp_size(mesh)
    N_pad = (N + dpn - 1) // dpn * dpn
    if N_pad != N:
        Xs = jnp.pad(Xs, ((0, N_pad - N), (0, 0)))
    with jax.set_mesh(mesh), hints.activate(mesh):
        dp = hints.dp_axes(mesh)
        f = jax.jit(
            _predict_fn,
            in_shardings=(
                NamedSharding(mesh, P(dp, None)), None, None, None, None, None,
            ),
        )
        mu, var = f(Xs, u, chol, sqrtlam, spec, idx)
    return mu[:N], var[:N]


# ---------------------------------------------------------------------------
# v2 schedule (§Perf iteration 1): explicit shard_map
#
# Baseline (v1) constrained G to ("model", None) every scan step, which made
# XLA all-gather each Phi block over dp and reshard the Gram each iteration:
# 439 GB of wire per device for fit_8m (collective term 8.78 s) and 16-32x
# redundant compute.  v2 shards rows over EVERY mesh axis, streams the local
# Gram in-shard, and reduces ONCE:  wire = 2 x |G| = 1.7 GB -> ~34 ms, and
# compute = 2NM^2 / n_chips exactly.  Prediction is fully local per shard
# (u, Binv replicated): zero per-row collectives.
# ---------------------------------------------------------------------------


def _fit_fn_v2(X, y, spec: GPSpec, idx, nblk: int, mesh,
               n_valid: int | None = None, backend: str = "jnp", aux=None):
    exp = get_expansion(spec.expansion)
    N = X.shape[0]
    M = idx.shape[0]
    sig2 = spec.noise**2
    loglam = exp.log_eigenvalues(idx, spec)
    axes = tuple(mesh.axis_names)
    n_chips = int(np.prod([mesh.shape[a] for a in axes]))
    N_l = N // n_chips
    block = max(1, N_l // nblk)

    def local(Xl, yl, eps, rho, *omega_t):
        lo = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            lo = lo * mesh.shape[a] + jax.lax.axis_index(a)
        row0 = lo * N_l
        s_loc = _spec_local(spec, eps, rho, omega_t[0] if omega_t else None)

        if backend != "jnp":
            # registry path: the whole shard's moments in ONE streaming
            # fused-kernel call (feature tiles generated in VMEM by the
            # expansion's tile builder, never in HBM)
            mask = None
            if n_valid is not None and n_valid < N:
                mask = ((row0 + jnp.arange(N_l)) < n_valid).astype(Xl.dtype)
            G_l, b_l = get_backend(backend).moments(
                Xl, yl, s_loc, idx, aux, block, mask
            )
        else:
            def step(carry, inp):
                G, b = carry
                i, Xi, yi = inp
                Phi_i = exp.features(Xi, idx, s_loc)
                if n_valid is not None and n_valid < N:
                    mask = ((row0 + i * block + jnp.arange(block)) < n_valid)
                    Phi_i = Phi_i * mask.astype(Phi_i.dtype)[:, None]
                    yi = yi * mask.astype(yi.dtype)
                return (G + Phi_i.T @ Phi_i, b + Phi_i.T @ yi), None

            nb = N_l // block
            (G_l, b_l), _ = jax.lax.scan(
                step,
                (jnp.zeros((M, M), Xl.dtype), jnp.zeros((M,), Xl.dtype)),
                (jnp.arange(nb), Xl.reshape(nb, block, -1), yl.reshape(nb, block)),
            )
        G = jax.lax.psum(G_l, axes)        # THE one collective (M x M)
        b = jax.lax.psum(b_l, axes)
        return G, b

    omega_args = _omega_args(spec)
    G, b = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes), P(axes), P(), P()) + (P(),) * len(omega_args),
        out_specs=(P(), P()),
        check_vma=False,
    )(X.reshape(N, -1), y, spec.eps, spec.rho, *omega_args)

    B, sqrtlam = _assemble_scaled_system(G, loglam, sig2)
    chol = jnp.linalg.cholesky(B)
    u = _solve_mean_weights(chol, sqrtlam, b, sig2)
    return u, chol, sqrtlam, b


def _predict_fn_v2(Xs, u, chol, sqrtlam, spec: GPSpec, idx, mesh):
    """Fully local per row: Binv replicated, var = rowsum((Phi D Binv)*(Phi D))."""
    exp = get_expansion(spec.expansion)
    M = idx.shape[0]
    Binv = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(M, dtype=chol.dtype))
    axes = tuple(mesh.axis_names)

    def local(Xl, u_, Binv_, sqrtlam_, eps, rho, *omega_t):
        s_loc = _spec_local(spec, eps, rho, omega_t[0] if omega_t else None)
        Phis = exp.features(Xl, idx, s_loc)
        mu = Phis @ u_
        PD = Phis * sqrtlam_[None, :]
        var = jnp.sum((PD @ Binv_) * PD, axis=1)
        return mu, var

    omega_args = _omega_args(spec)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes), P(), P(), P(), P(), P()) + (P(),) * len(omega_args),
        out_specs=(P(axes), P(axes)),
        check_vma=False,
    )(Xs, u, Binv, sqrtlam, spec.eps, spec.rho, *omega_args)


# ---------------------------------------------------------------------------
# Dry-run lowering (ShapeDtypeStructs only, no allocation)
# ---------------------------------------------------------------------------


def _abstract_spec(cfg, p: int) -> GPSpec:
    """Abstract (ShapeDtypeStruct-leaved) hermite GPSpec for a workload's
    FAGPConfig — the dry-run never allocates hyperparameters."""
    f32 = jnp.float32
    return GPSpec(
        eps=jax.ShapeDtypeStruct((p,), f32),
        rho=jax.ShapeDtypeStruct((p,), f32),
        noise=jax.ShapeDtypeStruct((), f32),
        n=cfg.n, index_set=cfg.index_set, degree=cfg.degree,
        block_rows=cfg.block_rows, store_train=cfg.store_train,
        backend=cfg.backend,
    )


_n_chips = shardspec.mesh_size


def lower_fit(wl, mesh, *, schedule: str = "v2"):
    idx_np = wl.cfg.indices(wl.p)
    idx = jnp.asarray(idx_np)
    spec_av = _abstract_spec(wl.cfg, wl.p)
    if schedule == "v2":
        quantum = _n_chips(mesh) * 16
        N_pad = (wl.N + quantum - 1) // quantum * quantum
        X = jax.ShapeDtypeStruct((N_pad, wl.p), jnp.float32)
        y = jax.ShapeDtypeStruct((N_pad,), jnp.float32)
        backend = wl.cfg.backend
        aux = (get_backend(backend).prepare(idx_np, spec_av)
               if backend != "jnp" else None)
        return jax.jit(
            partial(_fit_fn_v2, nblk=16, mesh=mesh,
                    n_valid=wl.N if N_pad != wl.N else None,
                    backend=backend, aux=aux),
        ).lower(X, y, spec_av, idx)
    nblk, N_pad = _pick_nblk(wl.N, idx_np.shape[0], _dp_size(mesh))
    X = jax.ShapeDtypeStruct((N_pad, wl.p), jnp.float32)
    y = jax.ShapeDtypeStruct((N_pad,), jnp.float32)
    dp = hints.dp_axes(mesh)
    return jax.jit(
        partial(_fit_fn, nblk=nblk,
                n_valid=wl.N if N_pad != wl.N else None),
        in_shardings=(
            NamedSharding(mesh, P(dp, None)), NamedSharding(mesh, P(dp)),
            None, None,
        ),
    ).lower(X, y, spec_av, idx)


def lower_predict(wl, mesh, *, schedule: str = "v2"):
    idx_np = wl.cfg.indices(wl.p)
    M = idx_np.shape[0]
    idx = jnp.asarray(idx_np)
    spec_av = _abstract_spec(wl.cfg, wl.p)
    u = jax.ShapeDtypeStruct((M,), jnp.float32)
    chol = jax.ShapeDtypeStruct((M, M), jnp.float32)
    sqrtlam = jax.ShapeDtypeStruct((M,), jnp.float32)
    if schedule == "v2":
        quantum = _n_chips(mesh)
        N_pad = (wl.N + quantum - 1) // quantum * quantum
        Xs = jax.ShapeDtypeStruct((N_pad, wl.p), jnp.float32)
        return jax.jit(
            partial(_predict_fn_v2, mesh=mesh),
        ).lower(Xs, u, chol, sqrtlam, spec_av, idx)
    dpn = _dp_size(mesh)
    N_pad = (wl.N + dpn - 1) // dpn * dpn
    Xs = jax.ShapeDtypeStruct((N_pad, wl.p), jnp.float32)
    dp = hints.dp_axes(mesh)
    return jax.jit(
        _predict_fn,
        in_shardings=(
            NamedSharding(mesh, P(dp, None)), None, None, None, None, None,
        ),
    ).lower(Xs, u, chol, sqrtlam, spec_av, idx)
