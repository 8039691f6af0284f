"""Fleet-scale batched GP hyperparameter optimization — the lane engine.

The paper's cheap-posterior claim only pays off once hyperparameters are
chosen, and its regime — many small decomposed GPs — is exactly where a
Python loop of per-model optimizers is dominated by per-step dispatch.
This module optimizes the log-space NLML of **every tenant and every
random restart at once**: one jitted AdamW step over a ``(B, R)`` *lane*
axis, where lane ``(t, r)`` is restart ``r`` of tenant ``t``.

Anatomy of a step (``_lane_step``):

* the objective is the masked NLML of ``core.fagp`` (``fagp._nlml_core``):
  the restart axis is **vmapped** (hyperparameters mapped, data shared)
  and the tenant axis is a **compiled scan** (``lax.map``) whose body is
  the identical per-tenant program — still ONE executable and one
  dispatch per step for the whole fleet, but, unlike a tenant-axis vmap,
  the per-tenant f32 arithmetic is bit-identical to a single-tenant run
  (batched lowering reorders reductions, which would drift trajectories
  past any usable parity bound; the scan makes the <= 1e-5 fleet-vs-loop
  gate assertable by construction).  The moment accumulation inside
  dispatches through the backend registry's ``moments`` hooks, so the
  optimization loop never materializes an N x M feature matrix on either
  backend (custom-VJP streamed backward; pinned by the jaxpr sweep in
  tests/test_gp_hyperopt.py);
* parameters are log-space leaves ``{log_eps, log_rho, log_noise}``
  (positivity by construction; the RFF spectral draws ``omega`` stay
  frozen — they are structure, not hyperparameters), stepped by
  ``repro.optim`` AdamW;
* **convergence masks**: a lane whose NLML improved by less than ``tol``
  freezes — its parameters AND its optimizer moments are carried through
  ``jnp.where`` unchanged, so frozen lanes stop moving bit-exactly while
  the step stays ONE fixed-shape executable (no recompiles as lanes
  converge; pinned via jit cache-miss counts);
* per-slot row masks express ragged per-tenant N on one fixed (B, N, p)
  stack, exactly as in ``GPBank.fit``.

Restart jitter is keyed by ``(seed, restart)`` only — every tenant sees
the SAME R perturbations of its init — so a loop of single-tenant runs
with the same seed reproduces the fleet's lanes exactly
(tests/test_gp_hyperopt.py::TestLaneEngine::
test_fleet_equals_loop_of_singles_exactly compares the two).

``optimize_fleet`` drives the loop and selects the best restart per tenant
by final NLML; ``optimize_restarts`` is the single-model wrapper that
``GP.optimize`` delegates to; ``GPBank.optimize`` refits the winners back
into its stacked state.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fagp
from repro.optim import adamw

__all__ = ["HyperoptResult", "optimize_fleet", "optimize_restarts"]

_FIELDS = ("log_eps", "log_rho", "log_noise")


@dataclasses.dataclass(frozen=True)
class HyperoptResult:
    """Per-tenant winners plus the full lane picture.

    eps/rho (B, p) and noise (B,) are the best restart's hyperparameters in
    natural space; ``nlml`` (B,) is that lane's final NLML per data row
    (the selection criterion); ``lane_nlml`` (B, R) keeps every restart's
    final value; ``frozen`` (B, R) marks lanes the convergence mask froze
    before the step budget ran out.
    """

    eps: jax.Array            # (B, p)
    rho: jax.Array            # (B, p)
    noise: jax.Array          # (B,)
    nlml: jax.Array           # (B,)   best lane's final NLML / row
    lane_nlml: jax.Array      # (B, R) every lane's final NLML / row
    best_restart: jax.Array   # (B,)
    frozen: np.ndarray        # (B, R)
    steps_run: int

    def spec_for(self, spec, t: int = 0):
        """The input spec with tenant ``t``'s learned hyperparameters."""
        return spec.replace(
            eps=self.eps[t], rho=self.rho[t], noise=self.noise[t]
        )


def _hp_to_spec(spec, hp):
    return spec.replace(
        eps=jnp.exp(hp["log_eps"]),
        rho=jnp.exp(hp["log_rho"]),
        noise=jnp.exp(hp["log_noise"]),
    )


def _init_lanes(spec, B: int, R: int, seed: int, jitter: float,
                init: Optional[dict]):
    """(B, R)-lane log-space parameter stack.  ``init`` optionally supplies
    per-tenant natural-space starting points {eps (B,p), rho (B,p),
    noise (B,)} (a heterogeneous bank re-optimizing from its current
    hyperparameters); otherwise every tenant starts from the spec.

    Restart 0 is always the unperturbed init; restarts 1..R-1 add
    N(0, jitter^2) log-space noise keyed by (seed, field, restart) ONLY —
    identical draws for every tenant, so single-tenant reruns with the
    same seed land on identical lanes."""
    if init is None:
        base = {
            "log_eps": jnp.broadcast_to(jnp.log(spec.eps),
                                        (B,) + spec.eps.shape),
            "log_rho": jnp.broadcast_to(jnp.log(spec.rho),
                                        (B,) + spec.rho.shape),
            "log_noise": jnp.broadcast_to(
                jnp.log(jnp.asarray(spec.noise, jnp.float32)), (B,)
            ),
        }
    else:
        base = {
            "log_eps": jnp.log(jnp.asarray(init["eps"], jnp.float32)),
            "log_rho": jnp.log(jnp.asarray(init["rho"], jnp.float32)),
            "log_noise": jnp.log(jnp.asarray(init["noise"], jnp.float32)),
        }
        for f, nd in (("log_eps", 2), ("log_rho", 2), ("log_noise", 1)):
            if base[f].ndim != nd or base[f].shape[0] != B:
                raise ValueError(
                    f"init[{f[4:]!r}] must have leading dim B={B} and "
                    f"ndim {nd}, got {base[f].shape}"
                )
    out = {}
    for f, key in zip(_FIELDS, jax.random.split(jax.random.PRNGKey(seed), 3)):
        leaf = base[f].astype(jnp.float32)            # (B,) + shape
        tiled = jnp.broadcast_to(leaf[:, None], (B, R) + leaf.shape[1:])
        if R > 1 and jitter:
            noise = jax.random.normal(
                key, (R,) + leaf.shape[1:], jnp.float32
            ) * jitter
            noise = noise.at[0].set(0.0)
            tiled = tiled + noise[None]
        out[f] = tiled
    return out


def _where_lanes(cond, a, b):
    """Select a/b per lane: cond (B, R) broadcast over trailing axes."""
    return jnp.where(cond.reshape(cond.shape + (1,) * (a.ndim - 2)), a, b)


_LANE_CLIP = 10.0


def _clip_per_lane(grads, clip: float):
    """Per-LANE gradient-norm clipping.  AdamW's built-in clip uses the
    global norm of the whole pytree, which would couple every tenant and
    restart through one shared scale — a B-tenant fleet would then follow a
    different trajectory than B single-tenant runs.  Clipping each (t, r)
    lane by its own norm keeps lanes exactly independent (the fleet-vs-loop
    parity gate depends on this)."""
    sq = sum(
        jnp.sum(
            jnp.square(g.astype(jnp.float32)),
            axis=tuple(range(2, g.ndim)),
        )
        for g in grads.values()
    )                                                   # (B, R)
    scale = jnp.minimum(1.0, clip / (jnp.sqrt(sq) + 1e-9))
    return {
        f: g * scale.reshape(scale.shape + (1,) * (g.ndim - 2))
        for f, g in grads.items()
    }


def _lane_loss(hp, X, y, mask, spec, idx):
    """One lane's objective: masked NLML per data row at exp(hp).  ``idx``
    rides along purely so the traced table is shared; the masked core
    re-derives it from the spec's static metadata."""
    del idx  # derived inside _nlml_core from the spec's static metadata
    sp = _hp_to_spec(spec, hp)
    return fagp._nlml_core(X, y, sp, mask) / jnp.maximum(jnp.sum(mask), 1.0)


@partial(jax.jit, static_argnames=("ocfg",))
def _lane_step(hp, ostate, frozen, prev, Xb, yb, maskb, spec, idx, tol,
               ocfg):
    """One AdamW step over every (tenant, restart) lane.

    Returns (hp, ostate, frozen, prev, vals) where ``vals`` (B, R) is the
    loss at the INPUT parameters.  A lane freezes when its improvement
    since the previous step falls below ``tol``; frozen lanes carry their
    parameters and optimizer moments through unchanged (bit-exact), and
    the executable is keyed only on the stack shapes — convergence
    patterns, masks and data churn never recompile it."""
    vg = jax.value_and_grad(_lane_loss)
    per_restart = jax.vmap(vg, in_axes=(0, None, None, None, None, None))
    vals, grads = jax.lax.map(
        lambda args: per_restart(*args, spec, idx), (hp, Xb, yb, maskb)
    )
    frozen = frozen | (prev - vals < tol)
    grads = _clip_per_lane(grads, _LANE_CLIP)
    new_hp, new_ostate, _ = adamw.apply_updates(hp, grads, ostate, ocfg)
    hp = {f: _where_lanes(frozen, hp[f], new_hp[f]) for f in hp}
    mu = {
        f: {
            k: _where_lanes(frozen, ostate["mu"][f][k], new_ostate["mu"][f][k])
            for k in ("m", "v")
        }
        for f in hp
    }
    ostate = {"mu": mu, "step": new_ostate["step"]}
    prev = jnp.where(frozen, prev, vals)
    return hp, ostate, frozen, prev, vals


@jax.jit
def _lane_values(hp, Xb, yb, maskb, spec, idx):
    """Final per-lane NLML/row at the CURRENT parameters (the best-restart
    selection criterion — ``_lane_step``'s vals lag one update behind)."""
    per_restart = jax.vmap(_lane_loss, in_axes=(0, None, None, None, None,
                                                None))
    return jax.lax.map(
        lambda args: per_restart(*args, spec, idx), (hp, Xb, yb, maskb)
    )


def _compose_obs_callback(user_cb, metrics, tracer):
    """Wrap the optimize_fleet progress-callback contract with telemetry:
    the observer fires first (round counter + step/best-NLML gauges + a
    ``hyperopt_progress`` instant event), then the user's callback, with
    exactly the ``(step, vals, hp)`` arguments the contract specifies."""
    counter = gauge_step = gauge_best = None
    if metrics is not None:
        counter = metrics.counter(
            "hyperopt_rounds_total", "progress-callback firings")
        gauge_step = metrics.gauge(
            "hyperopt_step", "current optimizer step")
        gauge_best = metrics.gauge(
            "hyperopt_best_nlml", "best lane NLML/row at the last firing")

    def cb(step, vals, hp):
        if counter is not None:
            counter.inc()
            gauge_step.set(step)
            gauge_best.set(float(np.min(vals)))
        if tracer is not None:
            tracer.instant("hyperopt_progress", step=int(step),
                           best_nlml=float(np.min(vals)))
        if user_cb is not None:
            user_cb(step, vals, hp)

    return cb


def optimize_fleet(
    Xb: jax.Array,
    yb: jax.Array,
    spec,
    *,
    mask: Optional[jax.Array] = None,
    restarts: int = 4,
    steps: int = 100,
    lr: float = 5e-2,
    tol: Optional[float] = None,
    jitter: float = 0.3,
    seed: int = 0,
    init: Optional[dict] = None,
    callback: Optional[Callable] = None,
    metrics=None,
    tracer=None,
) -> HyperoptResult:
    """Batched NLML hyperparameter learning for B independent tenants with
    R random restarts each — every lane in one compiled AdamW step.

    Xb (B, N, p), yb (B, N) (or (B, N, T) multi-output), mask (B, N) row
    validity for ragged per-tenant N.  ``tol`` (None = never) freezes lanes
    whose per-step NLML improvement drops below it; the loop exits early
    once every lane froze.  ``callback(step, vals, hp)`` fires every ~10%
    with the (B, R) loss snapshot and the raw log-space lane parameters.

    ``metrics`` / ``tracer`` (``repro.obs``) report per-round progress
    THROUGH that same callback contract — an internal observer composed
    in front of any user callback records a round counter, the current
    step and best lane NLML as gauges, and a ``hyperopt_progress``
    instant trace event per firing.  The optimization loop itself is
    untouched (no extra device syncs: the observer reads the ``vals``
    snapshot the callback already materializes).

    Returns a :class:`HyperoptResult` with the best restart per tenant
    selected by final NLML.
    """
    if metrics is not None or tracer is not None:
        callback = _compose_obs_callback(callback, metrics, tracer)
    Xb = jnp.asarray(Xb)
    yb = jnp.asarray(yb)
    if Xb.ndim != 3 or yb.ndim not in (2, 3) or yb.shape[:2] != Xb.shape[:2]:
        raise ValueError(
            f"optimize_fleet wants Xb (B, N, p) and yb (B, N[, T]); got "
            f"{Xb.shape} and {yb.shape}"
        )
    B, N, p = Xb.shape
    if restarts < 1 or steps < 1:
        raise ValueError("restarts and steps must be >= 1")
    fagp._check_p(spec, p)
    fagp._check_backend_support(spec)
    if mask is None:
        mask = jnp.ones((B, N), jnp.float32)
    else:
        mask = jnp.asarray(mask).astype(jnp.float32)
        if mask.shape != (B, N):
            raise ValueError(
                f"mask must be (B, N) = {(B, N)}, got {mask.shape}"
            )
    # small tenants: do not pad each slot's few rows up to the serving block
    spec = spec.replace(block_rows=min(spec.block_rows, max(1, N)))
    idx = jnp.asarray(spec.indices(p))

    # XLA inlines a LENGTH-1 tenant scan into its consumers, changing
    # fusion and therefore f32 rounding relative to the same tenant inside
    # a longer fleet; pad single-tenant runs to a length-2 scan (duplicate
    # tenant, sliced off below) so a lone GP.optimize run is bit-identical
    # to any fleet containing it — the fleet-vs-loop parity gate rests on
    # this invariance.
    B_run = B
    if B == 1:
        B_run = 2
        Xb = jnp.concatenate([Xb, Xb])
        yb = jnp.concatenate([yb, yb])
        mask = jnp.concatenate([mask, mask])
        if init is not None:
            init = {k: jnp.concatenate([jnp.asarray(v)] * 2) for k, v in
                    init.items()}

    hp = _init_lanes(spec, B_run, restarts, seed, jitter, init)
    # clip_norm=None: clipping happens per lane inside _lane_step (the
    # global-norm clip would couple every lane through one shared scale)
    ocfg = adamw.AdamWConfig(lr=lr, weight_decay=0.0, clip_norm=None)
    ostate = adamw.init(hp, ocfg)
    frozen = jnp.zeros((B_run, restarts), bool)
    prev = jnp.full((B_run, restarts), jnp.inf, jnp.float32)
    tol_f = jnp.float32(-jnp.inf if tol is None else tol)

    every = max(1, steps // 10)
    steps_run = steps
    for step in range(steps):
        hp, ostate, frozen, prev, vals = _lane_step(
            hp, ostate, frozen, prev, Xb, yb, mask, spec, idx, tol_f, ocfg
        )
        if callback is not None and (step % every == 0 or step == steps - 1):
            callback(step, np.asarray(vals)[:B], hp)
        if tol is not None and bool(jnp.all(frozen)):
            steps_run = step + 1
            break

    final = _lane_values(hp, Xb, yb, mask, spec, idx)      # (B_run, R)
    best = jnp.argmin(final, axis=1)                       # (B_run,)
    rows = jnp.arange(B_run)
    pick = lambda f: hp[f][rows, best]
    return HyperoptResult(
        eps=jnp.exp(pick("log_eps"))[:B],
        rho=jnp.exp(pick("log_rho"))[:B],
        noise=jnp.exp(pick("log_noise"))[:B],
        nlml=final[rows, best][:B],
        lane_nlml=final[:B],
        best_restart=best[:B],
        frozen=np.asarray(frozen)[:B],
        steps_run=steps_run,
    )


def optimize_restarts(
    X: jax.Array,
    y: jax.Array,
    spec,
    *,
    restarts: int = 1,
    steps: int = 100,
    lr: float = 5e-2,
    tol: Optional[float] = None,
    jitter: float = 0.3,
    seed: int = 0,
    callback: Optional[Callable] = None,
) -> HyperoptResult:
    """Single-model wrapper over :func:`optimize_fleet` (a B=1 fleet):
    multi-start gradient NLML learning for one dataset.  ``GP.optimize``
    delegates here; the result keeps its leading B=1 axis
    (``result.spec_for(spec)`` extracts the winner)."""
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    if X.ndim != 2:
        raise ValueError(f"optimize_restarts wants X (N, p), got {X.shape}")
    return optimize_fleet(
        X[None], y[None], spec, restarts=restarts, steps=steps, lr=lr,
        tol=tol, jitter=jitter, seed=seed, callback=callback,
    )
