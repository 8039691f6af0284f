"""Drive the FAGP fit, fleet-serving and Vecchia paths once on a TPU.

    python chip_smoke.py             # one chip: the three phases below
    python chip_smoke.py --chips 4   # four chips: sharded bank and
                                     # distributed fit, each against one chip

Phases on one chip:

1. ``fit``: the paper's single GP (``configs/fagp.py`` ``fit_10k``: N=10240,
   p=4, n=11, full grid, M=14641) through ``GP.fit(backend="pallas")``,
   ``mean_var`` on the 1024 held-out rows, ``update`` with 64 rows and
   ``mean_var`` again; checked against the same spec on ``backend="jnp"``
   and, by held-out RMSE, against the exact GP (``core/exact_gp.py``).
2. ``fleet``: a ``GPBank`` of 1024 tenants with 64 rows each (p=2, n=8,
   M=64) on ``backend="pallas"``, 4096 mixed-tenant tickets and one
   observe/ingest round through ``FleetEngine(BankRouter(bank))``; every
   answer is checked against ``GPBank.mean_var`` on ``backend="jnp"``, with
   no expired ticket and no recompile after warmup.
3. ``vecchia``: nearest-neighbour conditioning (k=32) on 10^4 clustered 2-D
   points, checked against the exact GP on a subset of the queries.

Each phase prints one JSON line: the device kind, compile and steady
seconds (informational, measured on that run's device), the agreement
numbers and the bounds they are checked against.  The last line is
``{"ok": true, "device": {...}}``.  A failed check exits non-zero without
that line, and so does a machine where JAX finds no TPU.  The script runs
everything in its own process, the only one that touches the chip.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Agreement bounds, set from the same comparisons run in float32 on the
# CPU backend (numbers in comments).
#
# fit: the pallas (interpreted) and jnp fits at M=14641 differ on the CPU
# by 1.05e-5 in mu and 1.63e-9 in var (var up to 2.0e-3): float32 rounding
# of two evaluations of the same features, amplified by B's conditioning.
# The bounds are about ten times that.  The held-out RMSE of FAGP and of
# the exact GP (noise 0.05) differ by 1.7e-5 on the CPU (0.049064 vs
# 0.049081); the looser bound allows 2% of that RMSE.
FIT_MU_ABS = 1e-4
FIT_VAR_ABS = 1.6e-8
FIT_RMSE_GAP = 1e-3
# fleet: pallas vs jnp bank answers at M=64 (64-row tenants) differ on the
# CPU by 2.4e-5 in mu and 9.3e-8 in var; about ten times that
FLEET_MU_ABS = 2.5e-4
FLEET_VAR_ABS = 1e-6
# vecchia (k=32) vs the exact GP on the first VECCHIA_SUBSET queries: the
# approximation's own gap, 0.090 in mu and 0.033 in var on the CPU, with
# half again as margin
VECCHIA_MU_ABS = 0.15
VECCHIA_VAR_ABS = 0.05
VECCHIA_N = 10_000
VECCHIA_SUBSET = 256
# the clustered-spatial workload, as the former CPU Vecchia benchmark set
# it (tests/test_vecchia.py::TestClusteredAccuracy pins the same shape at
# N=1500): bump length scale 0.15 -> eps = 1/(sqrt(2) * 0.15)
EPS = 4.714
NOISE = 0.02
K = 32
DATA_KW = dict(extent=6.0, length_scale=0.15, noise=0.02, n_bumps=120)
# --chips 4: sharded bank vs resident bank; the bank's own parity bound
# for a sharded fit (tests/test_shard_bank.py::TestShardedParity::
# test_eight_shard_fit_agreement).  The distributed fit
# is held to the FIT_* bounds: it also differs only in summation order.
SHARD_ABS = 5e-5


def _device_or_exit(count: int):
    """The TPU devices this run uses; exits non-zero without a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}"
        )
    if len(devs) < count:
        raise SystemExit(
            f"chip_smoke: --chips {count} needs {count} devices; JAX "
            f"found {len(devs)}"
        )
    return devs


def _timed(fn):
    """(result, wall seconds) of fn() run to completion on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


class Checks:
    """Agreement numbers of one phase, each next to its bound."""

    def __init__(self, phase: str, kind: str):
        self.line = {"phase": phase, "device_kind": kind,
                     "informational": {}, "agreement": {}, "bounds": {},
                     "failed": []}

    def info(self, **kw):
        self.line["informational"].update(kw)

    def le(self, name: str, value: float, bound: float):
        self.line["agreement"][name] = float(value)
        self.line["bounds"][name] = float(bound)
        if not float(value) <= bound:      # NaN fails
            self.line["failed"].append(name)

    def true(self, name: str, value: bool):
        self.line["agreement"][name] = bool(value)
        if not value:
            self.line["failed"].append(name)

    def finite(self, name: str, *arrays):
        import numpy as np

        self.true(f"{name}_finite",
                  all(bool(np.isfinite(np.asarray(a)).all()) for a in arrays))

    def emit(self) -> bool:
        self.line["ok"] = not self.line["failed"]
        print(json.dumps(self.line), flush=True)
        return self.line["ok"]


def _max_abs(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def _rmse(mu, y) -> float:
    import numpy as np

    return float(np.sqrt(np.mean((np.asarray(mu) - np.asarray(y)) ** 2)))


# -- phase 1: the paper's single GP -------------------------------------------


def _fit_problem():
    from repro.configs.fagp import SHAPES
    from repro.core.gp import GPSpec
    from repro.data import make_gp_dataset

    wl = SHAPES["fit_10k"]
    X, y, Xs, ys = make_gp_dataset(wl.N, wl.p, seed=0)
    Xu, yu, _, _ = make_gp_dataset(64, wl.p, seed=1)
    spec = GPSpec.create(
        wl.cfg.n, eps=[0.8] * wl.p, rho=2.0, noise=0.05,
        index_set=wl.cfg.index_set, store_train=wl.cfg.store_train,
        backend="pallas",
    )
    return spec, (X, y, Xs, ys), (Xu, yu)


def phase_fit(kind: str, cache_dir: str) -> bool:
    import jax
    import jax.numpy as jnp

    from repro.core import exact_gp, fagp
    from repro.core.gp import GP

    c = Checks("fit", kind)
    spec, (X, y, Xs, ys), (Xu, yu) = _fit_problem()

    # the first fit compiles; after the in-memory caches are dropped the
    # next one reads its programs back from the persistent cache
    gp, t_first = _timed(lambda: GP.fit(X, y, spec))
    gp, t_fit = _timed(lambda: GP.fit(X, y, spec))
    jax.clear_caches()
    gp, t_again = _timed(lambda: GP.fit(X, y, spec))
    print(json.dumps({"phase": "fit_compile", "device_kind": kind,
                      "informational": {
                          "compile_s": t_first - t_fit,
                          "compile_again_s": t_again - t_fit,
                          "cache_dir": cache_dir}}), flush=True)
    idx_np = spec.indices(spec.p)
    aux = fagp.get_backend("pallas").prepare(idx_np, spec)
    kernels = fagp._pallas_system.lower(X, y, spec, jnp.asarray(idx_np),
                                        aux).compile().as_text()
    c.true("tpu_custom_call_in_fit", "tpu_custom_call" in kernels)

    (mu, var), t_mv_first = _timed(lambda: gp.mean_var(Xs))
    (mu, var), t_mv = _timed(lambda: gp.mean_var(Xs))
    gp, t_up = _timed(lambda: gp.update(Xu, yu))
    mu_u, var_u = gp.mean_var(Xs)
    del gp
    c.info(compile_s=t_first - t_fit, compile_again_s=t_again - t_fit,
           fit_first_s=t_first, fit_steady_s=t_fit,
           mean_var_first_s=t_mv_first, mean_var_steady_s=t_mv,
           update_first_s=t_up)

    # reference 1: the same spec on the jnp backend
    gpj, t_jfit = _timed(lambda: GP.fit(X, y, spec.replace(backend="jnp")))
    muj, varj = gpj.mean_var(Xs)
    gpj = gpj.update(Xu, yu)
    muj_u, varj_u = gpj.mean_var(Xs)
    del gpj
    # reference 2: the exact GP at the same N
    st, t_exact = _timed(lambda: exact_gp.fit(X, y, spec.params))
    mue, vare = exact_gp.mean_var(st, Xs)
    del st
    c.info(jnp_fit_first_s=t_jfit, exact_fit_first_s=t_exact)

    c.finite("pallas", mu, var, mu_u, var_u)
    c.finite("jnp", muj, varj, muj_u, varj_u)
    c.finite("exact", mue, vare)
    c.le("mu_pallas_vs_jnp", _max_abs(mu, muj), FIT_MU_ABS)
    c.le("var_pallas_vs_jnp", _max_abs(var, varj), FIT_VAR_ABS)
    c.le("mu_pallas_vs_jnp_after_update", _max_abs(mu_u, muj_u), FIT_MU_ABS)
    c.le("var_pallas_vs_jnp_after_update", _max_abs(var_u, varj_u),
         FIT_VAR_ABS)
    r_p, r_e = _rmse(mu, ys), _rmse(mue, ys)
    c.info(rmse_pallas=r_p, rmse_exact=r_e, rmse_jnp=_rmse(muj, ys),
           rmse_pallas_after_update=_rmse(mu_u, ys))
    c.le("rmse_gap_pallas_vs_exact", abs(r_p - r_e), FIT_RMSE_GAP)
    return c.emit()


# -- phase 2: fleet serving ---------------------------------------------------

FLEET_B, FLEET_ROWS, FLEET_P, FLEET_N = 1024, 64, 2, 8
FLEET_TICKETS = 4096
FLEET_INGEST_TENANTS = 64


def _fleet_problem(backend: str):
    import numpy as np

    from repro.core.gp import GPSpec
    from repro.data import make_gp_dataset

    X, y, _, _ = make_gp_dataset(FLEET_B * FLEET_ROWS, FLEET_P, seed=0)
    Xb = X.reshape(FLEET_B, FLEET_ROWS, FLEET_P)
    yb = y.reshape(FLEET_B, FLEET_ROWS)
    rng = np.random.default_rng(0)
    tenants = [int(t) for t in rng.integers(0, FLEET_B, FLEET_TICKETS)]
    Xq = rng.uniform(-1, 1, (FLEET_TICKETS, FLEET_P)).astype(np.float32)
    spec = GPSpec.create(FLEET_N, eps=[0.8] * FLEET_P, rho=2.0, noise=0.05,
                         backend=backend)
    return spec, Xb, yb, tenants, Xq, rng


def _observations(rng, tenants):
    """One observation row per tenant: (ids, Xk (G, 1, p), yk (G, 1))."""
    import numpy as np

    Xk = rng.uniform(-1, 1, size=(len(tenants), 1, FLEET_P))
    yk = np.cos(Xk).sum(-1) + 0.05 * rng.standard_normal((len(tenants), 1))
    return list(tenants), Xk.astype(np.float32), yk.astype(np.float32)


def _serve(eng, tenants, Xq):
    """Submit every (tenant, row) ticket, drain, return results in order."""
    tickets = []
    out = {}
    for i, (t, x) in enumerate(zip(tenants, Xq)):
        tickets.append(eng.submit(t, x))
        if i % 1024 == 1023:
            out.update(eng.harvest())
    out.update(eng.drain())
    return [out.get(k) for k in tickets]


def _ingest(eng, ids, Xk, yk) -> int:
    """Observe one row per tenant through the engine, then ingest."""
    for t, x, yv in zip(ids, Xk, yk):
        eng.observe(t, x[0], yv[0])
    return eng.ingest()


def _warm_engine(bank, tenants, Xq, obs):
    """Compile what the measured traffic will run: every rung of the
    engine's bucket ladder and one ingest round.  A clock that ticks one
    second per reading keeps the engine's arrival-rate estimate low, so
    each drain dispatches exactly the rung that is pending.  Returns the
    bank after the ingest and the rungs that were dispatched."""
    from repro.bank import BankRouter, FleetEngine

    ticks = itertools.count()
    eng = FleetEngine(BankRouter(bank, microbatch=64), auto_pump=False,
                      clock=lambda: float(next(ticks)))
    for _ in range(2):          # the first pass seeds the rate estimates
        for rung in eng.buckets:
            _serve(eng, tenants[:rung], Xq[:rung])
    _ingest(eng, *obs)
    return eng.router.bank, sorted(eng.bucket_uses) == list(eng.buckets)


def phase_fleet(kind: str) -> bool:
    import jax
    import numpy as np

    from repro.bank import BankRouter, FleetEngine, GPBank
    from repro.obs import serving_watchdog

    c = Checks("fleet", kind)
    spec, Xb, yb, tenants, Xq, rng = _fleet_problem("pallas")
    _, t_fit_first = _timed(lambda: GPBank.fit(Xb, yb, spec).stack)
    bank, t_fit = _timed(lambda: GPBank.fit(Xb, yb, spec))
    perm = rng.permutation(FLEET_B)
    g = FLEET_INGEST_TENANTS
    rounds = [_observations(rng, [int(t) for t in perm[i * g:(i + 1) * g]])
              for i in range(2)]    # the warmup round, the measured round

    # the jnp bank's answers after each round, computed before the
    # watchdog is armed (it counts every compile of the bank executables)
    ref = GPBank.fit(Xb, yb, spec.replace(backend="jnp"))
    refs = []
    for obs in rounds:
        ref = ref.update(*obs)
        refs.append(ref.mean_var(tenants, Xq))
    (mu_ref, var_ref), (mu_ref_u, var_ref_u) = refs

    wd = serving_watchdog(mode="count")
    bank, warmed = _warm_engine(bank, tenants, Xq, rounds[0])
    eng = FleetEngine(BankRouter(bank, microbatch=64), watchdog=wd)
    wd.arm()

    t0 = time.perf_counter()
    before = _serve(eng, tenants, Xq)
    t_serve = time.perf_counter() - t0
    absorbed = _ingest(eng, *rounds[1])
    after = _serve(eng, tenants, Xq)
    wd.check("end")

    overall = eng.metrics()["overall"]
    answered = [r for r in before + after if r is not None]
    c.info(bank_fit_first_s=t_fit_first, bank_fit_steady_s=t_fit,
           serve_4096_s=t_serve,
           recompiled=[f"{ctx}: {grew}" for ctx, grew in wd.events],
           sustained_qps=overall["sustained_qps"], rows_absorbed=absorbed)
    c.true("every_rung_warmed", warmed)
    c.true("every_ticket_answered",
           len(answered) == 2 * FLEET_TICKETS
           and not any(r.timed_out for r in answered))
    c.le("expired", overall["expired"], 0)
    c.le("recompiles_after_warmup", wd.recompiles, 0)
    mu = np.array([r.mu for r in before])
    var = np.array([r.var for r in before])
    mu_u = np.array([r.mu for r in after])
    var_u = np.array([r.var for r in after])
    c.finite("served", mu, var, mu_u, var_u)
    c.finite("jnp", mu_ref, var_ref, mu_ref_u, var_ref_u)
    c.le("mu_engine_vs_jnp", _max_abs(mu, mu_ref), FLEET_MU_ABS)
    c.le("var_engine_vs_jnp", _max_abs(var, var_ref), FLEET_VAR_ABS)
    c.le("mu_engine_vs_jnp_after_ingest", _max_abs(mu_u, mu_ref_u),
         FLEET_MU_ABS)
    c.le("var_engine_vs_jnp_after_ingest", _max_abs(var_u, var_ref_u),
         FLEET_VAR_ABS)
    return c.emit()


# -- phase 3: Vecchia ---------------------------------------------------------


def phase_vecchia(kind: str) -> bool:
    import jax.numpy as jnp

    from repro.core import exact_gp
    from repro.core.gp import GP, GPSpec
    from repro.core.mercer import SEKernelParams
    from repro.data.gp_synthetic import make_clustered_dataset

    c = Checks("vecchia", kind)
    X, y, Xs, ys = make_clustered_dataset(VECCHIA_N, seed=0, **DATA_KW)
    spec = GPSpec.create_vecchia([EPS, EPS], NOISE, kernel="se",
                                 neighbors=K)
    (mu, var), t_first = _timed(lambda: GP.fit(X, y, spec).mean_var(Xs))
    (mu, var), t_steady = _timed(lambda: GP.fit(X, y, spec).mean_var(Xs))
    params = SEKernelParams(eps=jnp.asarray([EPS, EPS]),
                            rho=jnp.asarray(2.0), noise=jnp.asarray(NOISE))
    sub = slice(0, VECCHIA_SUBSET)
    mue, vare = exact_gp.mean_var(exact_gp.fit(X, y, params), Xs[sub])
    c.info(fit_mean_var_first_s=t_first, fit_mean_var_steady_s=t_steady,
           rmse_vecchia=_rmse(mu, ys), rmse_exact_subset=_rmse(mue, ys[sub]))
    c.finite("vecchia", mu, var)
    c.finite("exact", mue, vare)
    c.le("mu_vecchia_vs_exact", _max_abs(mu[sub], mue), VECCHIA_MU_ABS)
    c.le("var_vecchia_vs_exact", _max_abs(var[sub], vare), VECCHIA_VAR_ABS)
    return c.emit()


# -- --chips 4: the mesh paths ------------------------------------------------


def phase_sharded_bank(kind: str, devs) -> bool:
    import jax

    from repro.bank import GPBank, ShardedGPBank
    from repro.launch.mesh import make_bank_mesh

    c = Checks("sharded_bank", kind)
    spec, Xb, yb, tenants, Xq, rng = _fleet_problem("pallas")
    mesh = make_bank_mesh(4)
    resident = GPBank.fit(Xb, yb, spec)
    sharded, t_fit = _timed(
        lambda: ShardedGPBank.fit(Xb, yb, spec, mesh).stack
    )
    sharded = ShardedGPBank.fit(Xb, yb, spec, mesh)
    c.true("stack_on_4_devices",
           sharded.stack.chol.sharding.device_set == set(devs[:4])
           and len({s.device for s in sharded.stack.chol.addressable_shards})
           == 4)
    c.true("resident_on_device_0",
           resident.stack.chol.sharding.device_set == {devs[0]})
    (mu_s, var_s), t_serve = _timed(lambda: sharded.mean_var(tenants, Xq))
    mu_r, var_r = resident.mean_var(tenants, Xq)
    ids, Xk, yk = _observations(
        rng, [int(t) for t in rng.permutation(FLEET_B)[:FLEET_INGEST_TENANTS]]
    )
    resident = resident.update(ids, Xk, yk)
    sharded = sharded.update(ids, Xk, yk)
    mu_su, var_su = sharded.mean_var(tenants, Xq)
    mu_ru, var_ru = resident.mean_var(tenants, Xq)
    jax.block_until_ready((mu_su, mu_ru))
    c.info(sharded_fit_first_s=t_fit, sharded_mean_var_first_s=t_serve)
    c.finite("sharded", mu_s, var_s, mu_su, var_su)
    c.finite("resident", mu_r, var_r, mu_ru, var_ru)
    c.le("mu_sharded_vs_resident", _max_abs(mu_s, mu_r), SHARD_ABS)
    c.le("var_sharded_vs_resident", _max_abs(var_s, var_r), SHARD_ABS)
    c.le("mu_sharded_vs_resident_after_update", _max_abs(mu_su, mu_ru),
         SHARD_ABS)
    c.le("var_sharded_vs_resident_after_update", _max_abs(var_su, var_ru),
         SHARD_ABS)
    return c.emit()


def phase_distributed_fit(kind: str, devs) -> bool:
    import jax

    from repro.core import distributed
    from repro.core.gp import GP
    from repro.launch.mesh import make_local_mesh

    c = Checks("distributed_fit", kind)
    spec, (X, y, Xs, _), _ = _fit_problem()
    mesh = make_local_mesh(data=4, model=1)
    st, t_dist = _timed(lambda: distributed.fit_distributed(X, y, spec, mesh))
    c.true("fit_on_4_devices", st.chol.sharding.device_set == set(devs[:4]))
    mu_d, var_d = GP.from_state(jax.device_put(st, devs[0])).mean_var(Xs)
    del st
    gp, t_single = _timed(lambda: GP.fit(X, y, spec))
    mu, var = gp.mean_var(Xs)
    c.info(distributed_fit_first_s=t_dist, single_fit_first_s=t_single)
    c.finite("distributed", mu_d, var_d)
    c.finite("single", mu, var)
    c.le("mu_distributed_vs_single", _max_abs(mu_d, mu), FIT_MU_ABS)
    c.le("var_distributed_vs_single", _max_abs(var_d, var), FIT_VAR_ABS)
    return c.emit()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fit, fleet and vecchia phases on one chip; "
                         "4: sharded bank and distributed fit only")
    args = ap.parse_args(argv)
    devs = _device_or_exit(args.chips)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    kind = devs[0].device_kind
    if args.chips == 4:
        results = [phase_sharded_bank(kind, devs),
                   phase_distributed_fit(kind, devs)]
    else:
        results = [phase_fit(kind, cache_dir), phase_fleet(kind),
                   phase_vecchia(kind)]
    if not all(results):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
