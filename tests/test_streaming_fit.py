"""Tests for the streaming fused-fit engine and online incremental fitting.

Pins the three contracts of the tentpole:
  1. the fit's moments (ops.fused_fit_moments: features and Gram kernels,
     row chunk by row chunk) == materialize-then-reduce oracle, including
     row masks, ragged shapes and several chunks;
  2. fit(backend='pallas') materializes NO N x M intermediate (jaxpr sweep)
     while agreeing with the jnp scan fit to f32 tolerance;
  3. fit_update (rank-k Cholesky update) == full refit, for both hybrid
     branches (sequential sweep for small k, refactorization for large k),
     and update-then-predict == refit-then-predict.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import fagp, mercer
from repro.data import make_gp_dataset
from repro.kernels import ops, ref
from repro.kernels.hermite_phi import phi_tile
from repro.kernels.rff_phi import rff_consts_placeholder, rff_tile


def _setup(N, p, n_max, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(-2, 2, size=(N, p)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(N).astype(np.float32))
    eps = jnp.asarray(rng.uniform(0.3, 1.2, size=(p,)).astype(np.float32))
    rho = jnp.asarray(rng.uniform(1.5, 3.0, size=(p,)).astype(np.float32))
    idx = mercer.full_grid(n_max, p)
    consts = ref.phi_consts(eps, rho)
    S = jnp.asarray(ref.one_hot_selection(idx, n_max))
    M = idx.shape[0]
    d = jnp.asarray(np.geomspace(1.0, 1e-5, M).astype(np.float32))
    return X, y, consts, S, d


class TestFusedFitKernel:
    @pytest.mark.parametrize(
        "N,p,n_max",
        [
            (8, 1, 1),       # degenerate: single eigenvalue
            (100, 2, 6),     # ragged N
            (300, 3, 5),
            (513, 2, 9),     # ragged, off-pow2
            (1024, 4, 4),    # M = 256 = one full block
            (7, 1, 33),      # n_max past small unroll assumptions
        ],
    )
    def test_matches_materialized_oracle(self, N, p, n_max):
        X, y, consts, S, d = _setup(N, p, n_max)
        sig2 = jnp.float32(0.01)
        B, b = ops.fused_fit_moments(X, y, consts, S, d, sig2, n_max=n_max)
        Be, be = ref.ref_fused_fit_moments(X, y, consts, S, d, sig2, n_max)
        np.testing.assert_allclose(np.asarray(B), np.asarray(Be), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(b), np.asarray(be), rtol=1e-3, atol=1e-3)

    def test_unscaled_moments(self):
        X, y, consts, S, d = _setup(200, 2, 5)
        G, b = ops.fused_fit_moments(
            X, y, consts, S, d, jnp.float32(1.0), n_max=5, scale=False
        )
        Ge, be = ref.ref_fused_fit_moments(
            X, y, consts, S, d, jnp.float32(1.0), 5, scale=False
        )
        np.testing.assert_allclose(np.asarray(G), np.asarray(Ge), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(b), np.asarray(be), rtol=1e-3, atol=1e-3)

    def test_row_mask_excludes_rows(self):
        """Masked call == oracle on the kept subset (phi(0) != 0, so this
        exercises the in-kernel masking, not just zero padding)."""
        N = 150
        X, y, consts, S, d = _setup(N, 2, 6)
        keep = np.random.default_rng(3).uniform(size=N) > 0.4
        mask = jnp.asarray(keep.astype(np.float32))
        sig2 = jnp.float32(0.05)
        B, b = ops.fused_fit_moments(X, y, consts, S, d, sig2, mask, n_max=6)
        Be, be = ref.ref_fused_fit_moments(
            X[keep], y[keep], consts, S, d, sig2, 6
        )
        np.testing.assert_allclose(np.asarray(B), np.asarray(Be), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(b), np.asarray(be), rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("scale", [True, False])
    @pytest.mark.parametrize("expansion", ["hermite", "rff"])
    def test_row_chunks_match_oracle(self, expansion, scale):
        """N > padded M: the rows run through the two kernels in several
        chunks (600 rows, M padded to 128: five chunks of 128, the last
        with 40 padded rows), with masked rows across a chunk boundary."""
        N, p, n = 600, 2, 6
        X, y, consts, S, d = _setup(N, p, n)
        phi, tile = ref.ref_phi, phi_tile
        if expansion == "rff":
            rng = np.random.default_rng(4)
            w = rng.standard_normal((p, 24)).astype(np.float32)
            phase = np.repeat(np.float32([0.0, -np.pi / 2]), 24)
            S = jnp.asarray(np.vstack([np.hstack([w, w]), phase]))
            consts = rff_consts_placeholder()
            d = jnp.asarray(np.geomspace(1.0, 1e-2, 48).astype(np.float32))
            phi, tile = ref.ref_rff_phi, rff_tile
        M = S.shape[1]
        assert ops.fit_chunks(N, M)[2] >= 3
        keep = np.random.default_rng(6).uniform(size=N) > 0.2
        keep[200:300] = False                        # chunks 1 and 2
        mask = jnp.asarray(keep.astype(np.float32))
        sig2 = jnp.float32(0.02)
        B, b = ops.fused_fit_moments(X, y, consts, S, d, sig2, mask,
                                     n_max=n, scale=scale, tile_fn=tile)
        Be, be = ref.ref_fused_fit_moments(
            X[keep], y[keep], consts, S, d, sig2, n, scale=scale, phi=phi,
        )
        np.testing.assert_allclose(np.asarray(B), np.asarray(Be), rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(b), np.asarray(be), rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("expansion",
                             ["hermite", "rff_se", "rff_matern52"])
    def test_backend_moments_parity_with_mask(self, expansion):
        """Registry contract used by core.distributed: jnp and pallas
        moments agree on a masked shard, for every registered expansion."""
        N, p, n = 220, 2, 6
        X, y, *_ = make_gp_dataset(N, p, seed=1)
        if expansion == "hermite":
            spec = fagp.GPSpec.create(n, eps=[0.8] * p, rho=2.0, noise=0.05)
        else:
            spec = fagp.GPSpec.create_rff(
                [0.8] * p, noise=0.05, kernel=expansion[4:], num_features=48,
                seed=2,
            )
        idx = jnp.asarray(spec.indices(p))
        mask = jnp.asarray(
            (np.random.default_rng(5).uniform(size=N) > 0.3).astype(np.float32)
        )
        out = {}
        for name in ("jnp", "pallas"):
            be = fagp.get_backend(name)
            aux = be.prepare(np.asarray(idx), spec)
            out[name] = be.moments(X, y, spec, idx, aux, 64, mask)
        np.testing.assert_allclose(
            np.asarray(out["pallas"][0]), np.asarray(out["jnp"][0]),
            rtol=1e-3, atol=1e-3,
        )
        np.testing.assert_allclose(
            np.asarray(out["pallas"][1]), np.asarray(out["jnp"][1]),
            rtol=1e-3, atol=1e-3,
        )


def _iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into sub-jaxprs (pjit, scan, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vals = v if isinstance(v, (list, tuple)) else [v]
            for item in vals:
                inner = getattr(item, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)
                elif hasattr(item, "eqns"):
                    yield from _iter_eqns(item)


def _has_nxm_intermediate(fn, args, N, M):
    jaxpr = jax.make_jaxpr(fn)(*args)
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            # either orientation: an (N, M) Phi or its (M, N) transpose
            if len(shape) == 2 and max(shape) >= N and min(shape) >= M:
                return True
    return False


class TestNoMaterializedPhi:
    N, p, n = 600, 2, 6  # N well past any kernel block size; M = 36

    def _problem(self):
        X, y, *_ = make_gp_dataset(self.N, self.p, seed=0)
        params = mercer.SEKernelParams.create(
            jnp.full((self.p,), 0.8), jnp.full((self.p,), 2.0), 0.05
        )
        idx_np = mercer.full_grid(self.n, self.p)
        return X, y, params, idx_np

    def _spec(self, expansion, **kw):
        if expansion == "hermite":
            return fagp.GPSpec.create(
                self.n, eps=[0.8] * self.p, rho=2.0, noise=0.05, **kw
            )
        # R chosen so M = 2R > any kernel padding block won't hide an N x M
        return fagp.GPSpec.create_rff(
            [0.8] * self.p, noise=0.05, kernel=expansion[4:],
            num_features=32, seed=0, **kw,
        )

    @pytest.mark.parametrize("expansion",
                             ["hermite", "rff_se", "rff_matern52"])
    def test_streaming_fit_has_no_nxm(self, expansion):
        """The acceptance gate: no jaxpr intermediate of shape (>=N, >=M)
        anywhere in fit(backend='pallas', store_train=False) — for EVERY
        registered expansion (the RFF families fit streamed too)."""
        X, y, _, _ = self._problem()
        spec = self._spec(expansion, backend="pallas")
        idx = jnp.asarray(spec.indices(self.p))
        M = idx.shape[0]
        aux = fagp.get_backend("pallas").prepare(np.asarray(idx), spec)
        fn = lambda X, y: fagp._fit_pallas(X, y, spec, idx, aux).u
        assert not _has_nxm_intermediate(fn, (X, y), self.N, M)

    def test_checker_catches_materialized_path(self):
        """Sanity check of the checker itself: the materialized pipeline
        (hermite_phi -> scaled_gram) must trip it."""
        X, y, params, idx_np = self._problem()
        M = idx_np.shape[0]
        S = jnp.asarray(ref.one_hot_selection(idx_np, self.n))
        consts = ref.phi_consts(params.eps, params.rho)

        def materialized(X, y):
            Phi = ops.hermite_phi(X, consts, S, n_max=self.n)
            return ops.scaled_gram(Phi, jnp.ones((M,)), jnp.float32(0.01)), Phi.T @ y

        assert _has_nxm_intermediate(materialized, (X, y), self.N, M)

    @pytest.mark.parametrize("expansion",
                             ["hermite", "rff_se", "rff_matern52"])
    def test_jnp_scan_fit_has_no_nxm(self, expansion):
        """The jnp scan path holds the same O(M^2) bound (block_rows < N)."""
        X, y, _, _ = self._problem()
        spec = self._spec(expansion, block_rows=128)
        idx = jnp.asarray(spec.indices(self.p))
        M = idx.shape[0]
        fn = lambda X, y: fagp._fit(X, y, spec, idx).u
        assert not _has_nxm_intermediate(fn, (X, y), self.N, M)


class TestStreamingFitEngine:
    def test_pallas_fit_matches_jnp_fit(self):
        N, p, n = 700, 2, 8
        X, y, Xs, ys = make_gp_dataset(N, p, seed=2)
        spec = fagp.GPSpec.create(
            n, eps=jnp.full((p,), 0.8), rho=2.0, noise=0.05
        )
        st_j = fagp.fit(X, y, spec)
        st_p = fagp.fit(X, y, spec.replace(backend="pallas"))
        np.testing.assert_allclose(
            np.asarray(st_p.u), np.asarray(st_j.u), rtol=5e-3, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(st_p.chol), np.asarray(st_j.chol), rtol=5e-3, atol=1e-3
        )
        np.testing.assert_allclose(
            np.asarray(st_p.b), np.asarray(st_j.b), rtol=5e-3, atol=1e-3
        )

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown backend"):
            fagp.get_backend("cuda")

    def test_registry_lists_both(self):
        assert {"jnp", "pallas"} <= set(fagp.available_backends())


class TestFitUpdate:
    def _fitted(self, backend, store_train=False, N=400, p=2, n=8):
        X, y, Xs, ys = make_gp_dataset(N, p, seed=4)
        spec = fagp.GPSpec.create(
            n, eps=jnp.full((p,), 0.8), rho=2.0, noise=0.05,
            backend=backend, store_train=store_train,
        )
        return X, y, Xs, spec, fagp.fit(X, y, spec)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("k", [4, 64])  # sweep branch / refactor branch
    def test_update_equals_refit(self, backend, k):
        X, y, Xs, spec, st = self._fitted(backend)
        Xn, yn, *_ = make_gp_dataset(k, 2, seed=11)
        up = fagp.fit_update(st, Xn, yn)
        re = fagp.fit(
            jnp.concatenate([X, Xn]), jnp.concatenate([y, yn]), spec
        )
        np.testing.assert_allclose(
            np.asarray(up.u), np.asarray(re.u), rtol=5e-3, atol=1e-4
        )
        mu_u, var_u = fagp.predict_mean_var(up, Xs)
        mu_r, var_r = fagp.predict_mean_var(re, Xs)
        np.testing.assert_allclose(
            np.asarray(mu_u), np.asarray(mu_r), rtol=1e-3, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(var_u), np.asarray(var_r), rtol=5e-3, atol=1e-6
        )

    def test_sequential_updates_track_refit(self):
        """Several ingest rounds compound without drifting from the refit."""
        X, y, Xs, spec, st = self._fitted("jnp")
        Xacc, yacc = X, y
        for r in range(3):
            Xn, yn, *_ = make_gp_dataset(16, 2, seed=20 + r)
            st = fagp.fit_update(st, Xn, yn)
            Xacc = jnp.concatenate([Xacc, Xn])
            yacc = jnp.concatenate([yacc, yn])
        re = fagp.fit(Xacc, yacc, spec)
        np.testing.assert_allclose(
            np.asarray(st.u), np.asarray(re.u), rtol=1e-2, atol=1e-4
        )
        mu_u, _ = fagp.predict_mean_var(st, Xs)
        mu_r, _ = fagp.predict_mean_var(re, Xs)
        np.testing.assert_allclose(
            np.asarray(mu_u), np.asarray(mu_r), rtol=2e-3, atol=2e-4
        )

    def test_update_extends_stored_train_set(self):
        """store_train=True: Phi/y grow, and mode='paper' prediction on the
        updated state equals the refit's."""
        X, y, Xs, spec, st = self._fitted(
            "jnp", store_train=True, N=120, n=6
        )
        Xn, yn, *_ = make_gp_dataset(10, 2, seed=31)
        up = fagp.fit_update(st, Xn, yn)
        assert up.Phi.shape[0] == X.shape[0] + 10
        assert up.y.shape[0] == X.shape[0] + 10
        re = fagp.fit(
            jnp.concatenate([X, Xn]), jnp.concatenate([y, yn]), spec
        )
        # paper mode forms the N x N approximate inverse in f32; extra
        # rounding vs the fused path is expected (same tolerance as
        # test_fagp's paper-vs-fused comparison)
        mu_u, cov_u = fagp.predict(up, Xs[:9], mode="paper")
        mu_r, cov_r = fagp.predict(re, Xs[:9], mode="paper")
        np.testing.assert_allclose(
            np.asarray(mu_u), np.asarray(mu_r), atol=5e-3
        )
        np.testing.assert_allclose(
            np.asarray(cov_u), np.asarray(cov_r), atol=5e-3
        )

    def test_legacy_state_without_b_raises(self):
        _, _, _, _, st = self._fitted("jnp", N=64, n=4)
        legacy = dataclasses.replace(st, b=None)
        Xn, yn, *_ = make_gp_dataset(4, 2, seed=1)
        with pytest.raises(ValueError, match="fit_update"):
            fagp.fit_update(legacy, Xn, yn)


class TestServingLoop:
    def test_microbatched_equals_direct(self):
        from repro.launch.serve_gp import microbatched_mean_var

        N, p, n = 200, 2, 6
        X, y, Xs, ys = make_gp_dataset(N, p, seed=6)
        spec = fagp.GPSpec.create(
            n, eps=jnp.full((p,), 0.8), rho=2.0, noise=0.05
        )
        st = fagp.fit(X, y, spec)
        mu_d, var_d = fagp.predict_mean_var(st, Xs)
        mu_m, var_m, _ = microbatched_mean_var(st, Xs, microbatch=8)
        np.testing.assert_allclose(mu_m, np.asarray(mu_d), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(var_m, np.asarray(var_d), rtol=1e-5, atol=1e-7)

    def test_serve_gp_smoke(self):
        from repro.launch.serve_gp import serve_gp

        r = serve_gp(
            backend="jnp", n_train=96, p=1, n=6, rounds=2, update_size=16,
            queries=32, microbatch=16,
        )
        assert len(r["rounds"]) == 2
        assert r["rounds"][-1]["rows_absorbed"] == 96 + 2 * 16
        # posterior actually fits the cos target
        assert r["rounds"][-1]["rmse"] < 0.2
