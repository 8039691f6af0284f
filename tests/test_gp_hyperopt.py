"""Tests for fleet-scale batched hyperparameter optimization.

Pins the contracts of the gp_hyperopt tentpole:
  1. the masked NLML equals the NLML of the kept subset, and runs through
     the backend registry's moments hooks — value AND gradient agree
     between jnp and pallas, and the jaxpr of value_and_grad materializes
     no N x M intermediate for any registered expansion on either backend
     (the streaming-NLML sweep);
  2. the (B tenants x R restarts) lane engine: frozen lanes stop moving
     BIT-exactly while the step stays one executable (zero jit cache
     misses across mask/data/convergence churn), best-restart selection
     follows the final NLML, and a fleet run equals a loop of
     single-tenant runs EXACTLY (the scan-over-tenants construction);
  3. GPBank.optimize == loop of GP.optimize on both backends (the
     acceptance gate), and the heterogeneous bank it returns keeps every
     serving contract: per-slot hyperparameters in state()/mean_var(),
     update == fit_update, insert/evict with foreign hyperparameters,
     churn without recompiles;
  4. the router's staleness counters and reoptimize() hook.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.bank import BankRouter, GPBank
from repro.bank import bank as bank_mod
from repro.core import fagp
from repro.core.gp import GP, GPSpec
from repro.data import make_gp_dataset
from repro.optim import gp_hyperopt as gh

from test_streaming_fit import _has_nxm_intermediate, _iter_eqns


def _spec(expansion="hermite", p=2, n=5, backend="jnp", **kw):
    if expansion == "hermite":
        return GPSpec.create(n, eps=[0.8] * p, rho=2.0, noise=0.05,
                             backend=backend, **kw)
    return GPSpec.create_rff([0.8] * p, noise=0.05, kernel=expansion[4:],
                             num_features=16, seed=0, backend=backend, **kw)


def _fleet_data(B, N, p=2, seed=0):
    Xb = np.zeros((B, N, p), np.float32)
    yb = np.zeros((B, N), np.float32)
    for s in range(B):
        X, y, *_ = make_gp_dataset(N, p, seed=seed + s)
        Xb[s], yb[s] = np.asarray(X), np.asarray(y)
    return jnp.asarray(Xb), jnp.asarray(yb)


class TestMaskedNlml:
    def test_masked_equals_subset(self):
        N = 120
        X, y, *_ = make_gp_dataset(N, 2, seed=1)
        spec = _spec()
        keep = np.random.default_rng(2).uniform(size=N) > 0.35
        masked = float(fagp.nlml(X, y, spec,
                                 mask=jnp.asarray(keep.astype(np.float32))))
        subset = float(fagp.nlml(X[jnp.asarray(np.flatnonzero(keep))],
                                 y[jnp.asarray(np.flatnonzero(keep))], spec))
        assert masked == pytest.approx(subset, rel=1e-4, abs=1e-3)

    def test_mask_shape_validated(self):
        X, y, *_ = make_gp_dataset(16, 2, seed=0)
        with pytest.raises(ValueError, match="mask must be"):
            fagp.nlml(X, y, _spec(), mask=jnp.ones((4,)))

    def test_data_cotangents_survive_the_custom_vjp(self):
        """nlml stays differentiable through the DATA (X, y), not just the
        hyperparameters: the moments custom-VJP must propagate data
        cotangents (regression — an early draft returned zeros, silently
        corrupting input-side gradients)."""
        X, y, *_ = make_gp_dataset(80, 2, seed=0)
        spec = _spec(n=5)
        idx = jnp.asarray(spec.indices(2))

        def ref_nlml(X, y):
            # the fully-differentiable inline-moments path as the oracle
            exp = fagp.get_expansion(spec.expansion)
            N = X.shape[0]
            sig2 = spec.noise**2
            loglam = exp.log_eigenvalues(idx, spec)
            G, b = fagp._accumulate_moments(X, y, spec, idx, N)
            B, sqrtlam = fagp._assemble_scaled_system(G, loglam, sig2)
            chol = jnp.linalg.cholesky(B)
            bs = fagp._tscale(sqrtlam, b) / sig2
            w = jax.scipy.linalg.cho_solve((chol, True), bs)
            quad = jnp.sum(y * y) / sig2 - jnp.sum(bs * w)
            logdet = (2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
                      + N * jnp.log(sig2))
            return 0.5 * (quad + logdet + N * jnp.log(2.0 * jnp.pi))

        g_ref_y = jax.grad(ref_nlml, argnums=1)(X, y)
        g_ref_X = jax.grad(ref_nlml, argnums=0)(X, y)
        for backend in ("jnp", "pallas"):
            sp = spec.replace(backend=backend)
            g_y = jax.grad(lambda yy: fagp.nlml(X, yy, sp))(y)
            g_X = jax.grad(lambda XX: fagp.nlml(XX, y, sp))(X)
            np.testing.assert_allclose(np.asarray(g_y), np.asarray(g_ref_y),
                                       atol=1e-2, rtol=1e-3)
            np.testing.assert_allclose(np.asarray(g_X), np.asarray(g_ref_X),
                                       atol=1e-2, rtol=1e-3)

    def test_backends_agree_value_and_grad(self):
        """The registry-dispatched NLML: the pallas moments hook computes
        the value, the streamed custom-VJP the gradient — both must match
        the jnp path."""
        X, y, *_ = make_gp_dataset(200, 2, seed=3)
        out = {}
        for backend in ("jnp", "pallas"):
            spec0 = _spec(backend=backend, n=6)

            def loss(log_eps):
                sp = dataclasses.replace(spec0, eps=jnp.exp(log_eps))
                return fagp.nlml(X, y, sp)

            out[backend] = jax.value_and_grad(loss)(jnp.zeros(2))
        np.testing.assert_allclose(
            float(out["pallas"][0]), float(out["jnp"][0]), rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(out["pallas"][1]), np.asarray(out["jnp"][1]),
            rtol=1e-3, atol=1e-2,
        )


class TestStreamingNlml:
    """The streaming-NLML sweep: optimizing hyperparameters never
    materializes the N x M feature matrix — value and gradient, every
    registered expansion, both backends."""

    N = 600

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("expansion",
                             ["hermite", "rff_se", "rff_matern52"])
    def test_nlml_value_and_grad_have_no_nxm(self, expansion, backend):
        X, y, *_ = make_gp_dataset(self.N, 2, seed=0)
        spec = _spec(expansion, n=6, backend=backend, block_rows=64)
        M = spec.n_features(2)

        def loss(log_eps):
            sp = dataclasses.replace(spec, eps=jnp.exp(log_eps))
            mask = jnp.ones((X.shape[0],), jnp.float32)
            return fagp._nlml_core(X, y, sp, mask)

        fn = jax.value_and_grad(loss)
        assert not _has_nxm_intermediate(fn, (jnp.zeros(2),), self.N, M)

    def test_pallas_path_actually_runs_the_kernel(self):
        """Guard against the dispatch silently falling back to jnp: the
        pallas-backend NLML jaxpr must contain a pallas_call."""
        X, y, *_ = make_gp_dataset(self.N, 2, seed=0)
        spec = _spec(backend="pallas", n=6, block_rows=64)
        mask = jnp.ones((X.shape[0],), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda X, y: fagp._nlml_core(X, y, spec, mask)
        )(X, y)
        names = {eqn.primitive.name for eqn in _iter_eqns(jaxpr.jaxpr)}
        assert "pallas_call" in names


class TestLaneEngine:
    def _setup(self, B=3, N=16, R=2):
        Xb, yb = _fleet_data(B, N)
        spec = _spec().replace(block_rows=N)
        idx = jnp.asarray(spec.indices(2))
        hp = gh._init_lanes(spec, B, R, 0, 0.3, None)
        ocfg = gh.adamw.AdamWConfig(lr=5e-2, weight_decay=0.0,
                                    clip_norm=None)
        ostate = gh.adamw.init(hp, ocfg)
        maskb = jnp.ones((B, N), jnp.float32)
        return Xb, yb, maskb, spec, idx, hp, ocfg, ostate

    def test_frozen_lanes_stop_moving_bitwise(self):
        """A frozen lane's parameters AND optimizer moments are carried
        through unchanged — not 'small updates', NO updates."""
        Xb, yb, maskb, spec, idx, hp, ocfg, ostate = self._setup()
        B, R = 3, 2
        prev = jnp.full((B, R), jnp.inf, jnp.float32)
        frozen = jnp.zeros((B, R), bool)
        # one live step to get nonzero optimizer moments
        hp, ostate, frozen, prev, _ = gh._lane_step(
            hp, ostate, frozen, prev, Xb, yb, maskb, spec, idx,
            jnp.float32(-jnp.inf), ocfg,
        )
        pattern = jnp.asarray(np.array([[True, False], [False, True],
                                        [True, True]]))
        hp2, ostate2, *_ = gh._lane_step(
            hp, ostate, pattern, prev, Xb, yb, maskb, spec, idx,
            jnp.float32(-jnp.inf), ocfg,
        )
        pat = np.asarray(pattern)
        for f in hp:
            moved = np.asarray(hp2[f]) != np.asarray(hp[f])
            moved = moved.reshape(pat.shape + (-1,)).any(axis=-1)
            assert not moved[pat].any()      # frozen lanes: bit-identical
            assert moved[~pat].all()         # live lanes: actually moved
            for k in ("m", "v"):
                m_moved = (np.asarray(ostate2["mu"][f][k])
                           != np.asarray(ostate["mu"][f][k]))
                m_moved = m_moved.reshape(pat.shape + (-1,)).any(axis=-1)
                assert not m_moved[pat].any()

    def test_step_executable_reused_across_churn(self):
        """Convergence patterns, row masks and data churn never recompile
        the lane step (shapes key the cache, values do not)."""
        Xb, yb, maskb, spec, idx, hp, ocfg, ostate = self._setup()
        B, R = 3, 2
        prev = jnp.full((B, R), jnp.inf, jnp.float32)
        frozen = jnp.zeros((B, R), bool)
        args = (hp, ostate, frozen, prev, Xb, yb, maskb, spec, idx,
                jnp.float32(-jnp.inf), ocfg)
        gh._lane_step(*args)
        size0 = gh._lane_step._cache_size()
        rng = np.random.default_rng(0)
        for _ in range(3):
            maskc = jnp.asarray(
                (rng.uniform(size=maskb.shape) > 0.3).astype(np.float32)
            )
            frozenc = jnp.asarray(rng.uniform(size=(B, R)) > 0.5)
            gh._lane_step(hp, ostate, frozenc, prev, Xb * 1.1, yb, maskc,
                          spec, idx, jnp.float32(1e-4), ocfg)
        assert gh._lane_step._cache_size() == size0

    def test_tol_freezes_and_early_exits(self):
        Xb, yb = _fleet_data(2, 16)
        res = gh.optimize_fleet(Xb, yb, _spec(), restarts=2, steps=50,
                                tol=1e9, seed=0)
        assert res.steps_run < 50
        assert res.frozen.all()

    def test_restart_selection_follows_final_nlml(self):
        Xb, yb = _fleet_data(2, 16)
        res = gh.optimize_fleet(Xb, yb, _spec(), restarts=3, steps=5,
                                seed=1)
        lane = np.asarray(res.lane_nlml)
        np.testing.assert_array_equal(np.asarray(res.best_restart),
                                      lane.argmin(axis=1))
        np.testing.assert_allclose(np.asarray(res.nlml), lane.min(axis=1))
        assert res.eps.shape == (2, 2) and res.noise.shape == (2,)

    def test_fleet_equals_loop_of_singles_exactly(self):
        """The parity construction: per-tenant lane math is bit-identical
        between a fleet run and single-tenant runs (scan over tenants,
        length-1 padded)."""
        B = 4
        Xb, yb = _fleet_data(B, 16)
        spec = _spec()
        res = gh.optimize_fleet(Xb, yb, spec, restarts=2, steps=6, seed=2)
        for t in range(B):
            one = gh.optimize_restarts(Xb[t], yb[t], spec, restarts=2,
                                       steps=6, seed=2)
            np.testing.assert_array_equal(np.asarray(res.eps[t]),
                                          np.asarray(one.eps[0]))
            np.testing.assert_array_equal(np.asarray(res.rho[t]),
                                          np.asarray(one.rho[0]))
            np.testing.assert_array_equal(np.asarray(res.noise[t]),
                                          np.asarray(one.noise[0]))
            np.testing.assert_array_equal(np.asarray(res.nlml[t]),
                                          np.asarray(one.nlml[0]))

    def test_gp_optimize_restarts_picks_best(self):
        X, y, *_ = make_gp_dataset(64, 2, seed=4)
        spec = _spec()
        multi = gh.optimize_restarts(X, y, spec, restarts=3, steps=8,
                                     seed=0)
        single = gh.optimize_restarts(X, y, spec, restarts=1, steps=8,
                                      seed=0)
        # restart 0 of the multi run is the single run's lane (the restart
        # axis is vmapped, so R=1 vs R=3 lower differently — agreement is
        # to batched-GEMM rounding, unlike the bit-exact tenant axis)
        np.testing.assert_allclose(float(multi.lane_nlml[0, 0]),
                                   float(single.nlml[0]), rtol=1e-3)
        assert float(multi.nlml[0]) <= float(multi.lane_nlml[0, 0]) + 1e-6
        gp = GP.optimize(X, y, spec, restarts=3, steps=8, seed=0)
        best = multi.spec_for(spec, 0)
        np.testing.assert_array_equal(np.asarray(gp.spec.eps),
                                      np.asarray(best.eps))


class TestGPBankOptimize:
    def _bank(self, B=3, N=16, backend="jnp", seed=0):
        Xb, yb = _fleet_data(B, N, seed=seed)
        spec = _spec(backend=backend)
        return GPBank.fit(Xb, yb, spec), Xb, yb, spec

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_bank_optimize_matches_gp_loop(self, backend):
        """The acceptance gate at test scale: GPBank.optimize selects the
        same hyperparameters as a loop of GP.optimize runs (exactly), and
        the refit bank serves the same posterior as GP.fit at the learned
        values."""
        bank, Xb, yb, spec = self._bank(backend=backend)
        opt = bank.optimize(Xb, yb, restarts=2, steps=6, seed=5)
        assert opt.hypers is not None
        rng = np.random.default_rng(1)
        Xq = jnp.asarray(rng.uniform(-1, 1, (6, 2)).astype(np.float32))
        for t in range(3):
            gp = GP.optimize(Xb[t], yb[t], spec, restarts=2, steps=6,
                             seed=5)
            st = opt.state(t)
            np.testing.assert_array_equal(np.asarray(st.spec.eps),
                                          np.asarray(gp.spec.eps))
            np.testing.assert_array_equal(np.asarray(st.spec.rho),
                                          np.asarray(gp.spec.rho))
            np.testing.assert_array_equal(np.asarray(st.spec.noise),
                                          np.asarray(gp.spec.noise))
            m1, v1 = gp.mean_var(Xq)
            m2, v2 = opt.mean_var([t] * 6, Xq)
            np.testing.assert_allclose(np.asarray(m2), np.asarray(m1),
                                       rtol=5e-3, atol=2e-4)
            np.testing.assert_allclose(np.asarray(v2), np.asarray(v1),
                                       rtol=5e-3, atol=2e-4)

    def test_optimize_subset_leaves_others_untouched(self):
        bank, Xb, yb, spec = self._bank()
        opt = bank.optimize(Xb[1:2], yb[1:2], tenant_ids=[1], restarts=2,
                            steps=5, seed=0)
        Xq = jnp.asarray(
            np.random.default_rng(2).uniform(-1, 1, (4, 2)).astype(np.float32)
        )
        m0a, v0a = bank.mean_var([0] * 4, Xq)
        m0b, v0b = opt.mean_var([0] * 4, Xq)
        np.testing.assert_allclose(np.asarray(m0b), np.asarray(m0a),
                                   atol=1e-6)
        # untouched tenants keep the bank spec's hyperparameters
        st0 = opt.state(0)
        np.testing.assert_array_equal(np.asarray(st0.spec.eps),
                                      np.asarray(spec.eps))
        assert float(opt.state(1).spec.noise) != float(spec.noise)

    def test_hetero_update_matches_fit_update(self):
        bank, Xb, yb, spec = self._bank()
        opt = bank.optimize(Xb, yb, restarts=2, steps=5, seed=3)
        rng = np.random.default_rng(4)
        Xk = jnp.asarray(rng.uniform(-1, 1, (2, 4, 2)).astype(np.float32))
        yk = jnp.asarray(rng.standard_normal((2, 4)).astype(np.float32))
        Xq = jnp.asarray(rng.uniform(-1, 1, (5, 2)).astype(np.float32))
        up = opt.update([0, 2], Xk, yk)
        for g, t in enumerate((0, 2)):
            st = fagp.fit_update(opt.state(t), Xk[g], yk[g])
            m1, v1 = fagp.predict_mean_var(st, Xq)
            m2, v2 = up.mean_var([t] * 5, Xq)
            np.testing.assert_allclose(np.asarray(m2), np.asarray(m1),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(v2), np.asarray(v1),
                                       atol=1e-5)

    def test_hetero_insert_evict_roundtrip(self):
        """A heterogeneous bank admits tenants fitted under THEIR OWN
        hyperparameters (structure still shared), serves them correctly,
        and evict resets the slot to the bank spec's prior."""
        bank, Xb, yb, spec = self._bank()
        opt = bank.optimize(Xb, yb, restarts=2, steps=5, seed=6)
        ev = opt.evict(1)
        X, y, *_ = make_gp_dataset(16, 2, seed=50)
        foreign = fagp.fit(X, y, spec.replace(
            eps=jnp.asarray([1.5, 0.4], jnp.float32),
            noise=jnp.asarray(0.3, jnp.float32),
        ))
        ins = ev.insert("f", foreign)
        Xq = jnp.asarray(
            np.random.default_rng(3).uniform(-1, 1, (5, 2)).astype(np.float32)
        )
        m1, v1 = fagp.predict_mean_var(foreign, Xq)
        m2, v2 = ins.mean_var(["f"] * 5, Xq)
        np.testing.assert_allclose(np.asarray(m2), np.asarray(m1),
                                   rtol=1e-4, atol=1e-5)
        # the returned state round-trips the foreign hyperparameters
        np.testing.assert_array_equal(np.asarray(ins.state("f").spec.eps),
                                      np.asarray(foreign.spec.eps))
        # structural mismatch still refused
        other = fagp.fit(X, y, spec.replace(n=4))
        with pytest.raises(ValueError, match="expansion structure"):
            ins.evict("f").insert("g", other)

    def test_hetero_churn_without_recompile(self):
        """The heterogeneous serving and slot-write executables are keyed
        on the stack shapes only — churn through a hetero bank adds no jit
        cache entries."""
        bank, Xb, yb, spec = self._bank(B=3)
        opt = bank.optimize(Xb, yb, restarts=2, steps=4, seed=7)
        Xq = jnp.asarray(
            np.random.default_rng(5).uniform(-1, 1, (4, 2)).astype(np.float32)
        )
        opt = opt.evict(2)
        X, y, *_ = make_gp_dataset(16, 2, seed=60)
        opt = opt.insert("warm", (X, y))
        opt.mean_var(["warm", 0, 1, 0], Xq)
        writes0 = bank_mod._write_slot._cache_size()
        serve0 = bank_mod._hetero_gathered_mean_var._cache_size()
        b = opt
        for r in range(3):
            Xn, yn, *_ = make_gp_dataset(16, 2, seed=70 + r)
            b = b.evict("warm" if r == 0 else f"t{r - 1}")
            b = b.insert(f"t{r}", (Xn, yn))
            mu, _ = b.mean_var([f"t{r}", 0, 1, f"t{r}"], Xq)
            assert np.all(np.isfinite(np.asarray(mu)))
        assert bank_mod._write_slot._cache_size() == writes0
        assert bank_mod._hetero_gathered_mean_var._cache_size() == serve0

    def test_optimize_validates_inputs(self):
        bank, Xb, yb, spec = self._bank()
        with pytest.raises(ValueError, match="one tenant id per data row"):
            bank.optimize(Xb, yb, tenant_ids=[0, 1])
        with pytest.raises(ValueError, match="duplicate tenant"):
            bank.optimize(Xb, yb, tenant_ids=[0, 0, 1])
        with pytest.raises(ValueError, match="mask must be"):
            bank.optimize(Xb, yb, mask=jnp.ones((2, 2)))


class TestRouterReopt:
    def test_stale_counting_and_reoptimize(self):
        Xb, yb = _fleet_data(3, 16)
        spec = _spec()
        bank = GPBank.fit(Xb, yb, spec)
        router = BankRouter(bank, ingest_chunk=4)
        rng = np.random.default_rng(8)
        for t, cnt in ((0, 5), (2, 2)):
            for _ in range(cnt):
                router.observe(t, rng.uniform(-1, 1, 2).astype(np.float32),
                               float(rng.standard_normal()))
        assert router.ingest() == 7
        assert router.stale_tenants(3) == [0]
        assert set(router.stale_tenants(1)) == {0, 2}
        router.reoptimize([0], Xb[:1], yb[:1], restarts=2, steps=4, seed=0)
        assert router.bank.hypers is not None
        assert router.stale_tenants(1) == [2]
        # the swapped-in bank serves through the router
        tk = router.submit(0, np.zeros(2, np.float32))
        assert np.isfinite(router.flush()[tk][0])

    def test_reoptimize_empty_is_noop(self):
        Xb, yb = _fleet_data(2, 16)
        bank = GPBank.fit(Xb, yb, _spec())
        router = BankRouter(bank)
        router.reoptimize([], Xb[:0], yb[:0])
        assert router.bank is bank
