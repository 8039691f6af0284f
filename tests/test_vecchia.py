"""Vecchia nearest-neighbor conditioning — accuracy, memory, and protocol.

Four claims are pinned here:
  1. the blocked streaming k-NN (repro.kernels.knn) matches a dense O(N^2)
     numpy oracle (as index SETS per row — ties may be broken either way)
     and never materializes a Q x N distance matrix (jaxpr sweep, same
     methodology as tests/test_streaming_fit.py);
  2. vecchia converges to exact_gp as k -> N for BOTH reference kernels:
     prediction agrees to <= 1e-4 at full conditioning sets, the ordered-
     factorization NLML telescopes to the exact joint, and the error is
     (weakly) decreasing in k;
  3. on clustered 2-D spatial data (the regime it exists for) vecchia beats
     every registered global expansion at matched hyperparameters;
  4. the Approximation protocol: capability refusals are the structured
     UnsupportedError, checkpoints round-trip bit-exactly, update is an
     exact concatenation, and the facade dispatches both families.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import exact_gp, fagp, vecchia
from repro.core.approximation import (
    UnsupportedError,
    available_approximations,
    get_approximation,
)
from repro.core.gp import GP, GPSpec
from repro.core.mercer import SEKernelParams
from repro.data.gp_synthetic import make_clustered_dataset, make_gp_dataset
from repro.kernels import knn


def _points(N, p=2, seed=0, lo=-2.0, hi=2.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, (N, p)).astype(np.float32))


def _vecchia_problem(N=160, p=2, k=16, kernel="se", seed=0, noise=0.05):
    X, y, Xs, ys = make_gp_dataset(N, p, seed=seed)
    spec = GPSpec.create_vecchia([0.8] * p, noise, kernel=kernel,
                                 neighbors=k)
    return X, y, Xs, ys, spec


def _exact_params(spec):
    return SEKernelParams(eps=spec.eps, rho=spec.rho, noise=spec.noise)


# ---------------------------------------------------------------------------
# 1. the k-NN kernel
# ---------------------------------------------------------------------------


class TestKnnParity:
    @pytest.mark.parametrize("k,block_q,block_t", [
        (1, 128, 512), (7, 16, 32), (16, 33, 17), (40, 128, 512),
    ])
    def test_matches_dense_numpy_oracle(self, k, block_q, block_t):
        """Index SETS per row equal the O(Q x N) argsort (ties in distance
        may resolve to either index; the conditioning set is what matters)."""
        Xq, Xt = _points(57, seed=1), _points(143, seed=2)
        d, i = knn.knn_search(Xq, Xt, k, block_q=block_q, block_t=block_t)
        D = np.sum(
            (np.asarray(Xq)[:, None, :] - np.asarray(Xt)[None, :, :]) ** 2,
            axis=-1,
        )
        ref = np.argsort(D, axis=1, kind="stable")[:, :k]
        got_d = np.asarray(d)
        for r in range(Xq.shape[0]):
            assert set(np.asarray(i)[r]) == set(ref[r]), f"row {r}"
            np.testing.assert_allclose(
                got_d[r], np.sort(D[r])[:k], rtol=1e-4, atol=1e-5
            )
        # distances ascending per row
        assert np.all(np.diff(got_d, axis=1) >= -1e-7)

    def test_k_equals_n(self):
        Xq, Xt = _points(20, seed=3), _points(12, seed=4)
        _, i = knn.knn_search(Xq, Xt, 12, block_t=5)
        for r in range(20):
            assert set(np.asarray(i)[r]) == set(range(12))

    def test_bad_k_raises(self):
        X = _points(10)
        with pytest.raises(ValueError, match="1 <= k <= N"):
            knn.knn_search(X, X, 0)
        with pytest.raises(ValueError, match="1 <= k <= N"):
            knn.knn_search(X, X, 11)

    @pytest.mark.parametrize("block_q,block_t", [(128, 512), (13, 7)])
    def test_ordered_topk_matches_oracle(self, block_q, block_t):
        """Row i conditions on the nearest among j < i only; rows with
        fewer than k predecessors have exactly min(i, k) valid slots."""
        X = _points(71, seed=5)
        k = 9
        idx, mask = knn.ordered_topk(X, k, block_q=block_q, block_t=block_t)
        Xn = np.asarray(X)
        D = np.sum((Xn[:, None, :] - Xn[None, :, :]) ** 2, axis=-1)
        idx_n, mask_n = np.asarray(idx), np.asarray(mask)
        for r in range(71):
            nvalid = int(mask_n[r].sum())
            assert nvalid == min(r, k), f"row {r}"
            valid = set(idx_n[r][mask_n[r] > 0])
            ref = set(np.argsort(D[r, :r], kind="stable")[:k]) if r else set()
            assert valid == ref, f"row {r}"
            # masked slots are clamped in-bounds for safe gathers
            assert np.all(idx_n[r] >= 0) and np.all(idx_n[r] < 71)


class TestNoDenseDistanceMatrix:
    """The memory claim, pinned exactly like the streaming-fit tests: no
    intermediate in the whole jaxpr (scan/map bodies included) carries two
    axes that are both data-sized."""

    N, Q, k, LIMIT = 600, 400, 8, 256

    @staticmethod
    def _big_intermediate(fn, args, limit):
        from tests.test_streaming_fit import _iter_eqns

        jaxpr = jax.make_jaxpr(fn)(*args)
        for eqn in _iter_eqns(jaxpr.jaxpr):
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                big = [s for s in shape if s >= limit]
                if len(big) >= 2:
                    return eqn, shape
        return None

    def test_knn_search_streams(self):
        Xq, Xt = _points(self.Q, seed=0), _points(self.N, seed=1)
        hit = self._big_intermediate(
            lambda a, b: knn.knn_search(
                a, b, self.k, block_q=128, block_t=128
            ),
            (Xq, Xt), self.LIMIT,
        )
        assert hit is None, f"dense intermediate {hit[1]} in {hit[0]}"

    def test_checker_catches_dense_path(self):
        """Self-test: the sweep DOES flag a materialized Q x N matrix."""
        Xq, Xt = _points(self.Q, seed=0), _points(self.N, seed=1)
        hit = self._big_intermediate(
            lambda a, b: jnp.argsort(knn.sq_dists(a, b), axis=1)[:, :self.k],
            (Xq, Xt), self.LIMIT,
        )
        assert hit is not None

    def test_mean_var_streams(self):
        X, y = _points(self.N, seed=2), jnp.ones((self.N,))
        Xs = _points(self.Q, seed=3)
        spec = GPSpec.create_vecchia([0.8, 0.8], 0.05, neighbors=self.k,
                                     block_rows=128)
        g = GP.fit(X, y, spec)
        hit = self._big_intermediate(
            lambda a: g.mean_var(a), (Xs,), self.LIMIT
        )
        assert hit is None, f"dense intermediate {hit[1]} in {hit[0]}"

    def test_nlml_streams(self):
        X, y = _points(self.N, seed=4), jnp.ones((self.N,))
        spec = GPSpec.create_vecchia([0.8, 0.8], 0.05, neighbors=self.k,
                                     block_rows=128)
        hit = self._big_intermediate(
            lambda a, b: GP.fit(a, b, spec).nlml(a, b), (X, y), self.LIMIT
        )
        assert hit is None, f"dense intermediate {hit[1]} in {hit[0]}"


# ---------------------------------------------------------------------------
# 2. convergence to the exact GP
# ---------------------------------------------------------------------------


class TestExactAgreement:
    @pytest.mark.parametrize("kernel", ["se", "matern52"])
    def test_full_conditioning_matches_exact(self, kernel):
        """At k = N every query conditions on the whole training set: the
        prediction IS the exact GP's (<= 1e-4, the acceptance gate; noise
        0.1 keeps the f32 Cholesky well-conditioned — both sides factorize
        the same matrix under different row orders)."""
        X, y, Xs, _, spec = _vecchia_problem(N=160, k=160, kernel=kernel,
                                             noise=0.1)
        mu, var = GP.fit(X, y, spec).mean_var(Xs)
        st = exact_gp.fit(X, y, _exact_params(spec), kernel)
        mu_e, var_e = exact_gp.mean_var(st, Xs)
        np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_e),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(var), np.asarray(var_e),
                                   atol=1e-4)

    @pytest.mark.parametrize("kernel", ["se", "matern52"])
    def test_full_conditioning_matches_exact_on_clustered_data(self, kernel):
        """The same gate in the regime the family exists for: 256
        clustered 2-D points, short lengthscale (eps 4.714), noise 0.02,
        k = N - 1."""
        X, y, Xs, _ = make_clustered_dataset(
            256, extent=6.0, length_scale=0.15, noise=0.02, n_bumps=120,
            seed=0,
        )
        spec = GPSpec.create_vecchia([4.714, 4.714], 0.02, kernel=kernel,
                                     neighbors=255)
        mu, var = GP.fit(X, y, spec).mean_var(Xs)
        st = exact_gp.fit(X, y, _exact_params(spec), kernel)
        mu_e, var_e = exact_gp.mean_var(st, Xs)
        np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_e),
                                   rtol=0, atol=1e-4)
        np.testing.assert_allclose(np.asarray(var), np.asarray(var_e),
                                   rtol=0, atol=1e-4)

    @pytest.mark.parametrize("kernel", ["se", "matern52"])
    def test_nlml_telescopes_to_exact(self, kernel):
        """At k >= N-1 the ordered conditionals multiply back to the exact
        joint density (chain rule), so the NLMLs agree."""
        X, y, _, _, spec = _vecchia_problem(N=120, k=119, kernel=kernel)
        v = float(GP.fit(X, y, spec).nlml(X, y))
        e = float(exact_gp.nlml(X, y, _exact_params(spec), kernel))
        assert abs(v - e) <= 1e-3 * max(1.0, abs(e))

    def test_prediction_error_decreases_in_k(self):
        """|mu_k - mu_exact| is (weakly) decreasing along a k ladder."""
        X, y, Xs, _, spec = _vecchia_problem(N=200, k=4, noise=0.1)
        st = exact_gp.fit(X, y, _exact_params(spec), "se")
        mu_e, _ = exact_gp.mean_var(st, Xs)
        errs = []
        for k in (4, 16, 64, 200):
            mu, _ = GP.fit(X, y, spec.replace(neighbors=k)).mean_var(Xs)
            errs.append(float(jnp.max(jnp.abs(mu - mu_e))))
        assert errs[-1] <= 1e-4
        assert all(b <= a + 1e-6 for a, b in zip(errs, errs[1:])), errs

    def test_nlml_partial_conditioning_is_finite_and_ordered(self):
        """Small-k NLML is a valid (higher-entropy) bound-ish surrogate:
        finite, and moving k toward N moves it toward the exact value."""
        X, y, _, _, spec = _vecchia_problem(N=150, k=4)
        e = float(exact_gp.nlml(X, y, _exact_params(spec), "se"))
        gaps = []
        for k in (4, 32, 149):
            v = float(GP.fit(X, y, spec.replace(neighbors=k)).nlml(X, y))
            assert np.isfinite(v)
            gaps.append(abs(v - e))
        assert gaps[2] <= gaps[0]


# ---------------------------------------------------------------------------
# 3. the clustered-spatial regime
# ---------------------------------------------------------------------------


class TestClusteredAccuracy:
    def _data(self):
        return make_clustered_dataset(
            1500, extent=6.0, length_scale=0.15, noise=0.02, n_bumps=120,
            seed=0,
        )

    def test_beats_every_global_expansion(self):
        """The headline claim (chip_smoke.py's Vecchia phase runs the
        same workload at N=10^4 on a TPU): short-lengthscale clustered data
        defeats every global basis at matched hyperparameters, while
        nearest-neighbor conditioning tracks the local structure."""
        X, y, Xs, ys = self._data()
        eps = [4.714, 4.714]

        def rmse(mu):
            return float(jnp.sqrt(jnp.mean((mu - ys) ** 2)))

        v = GP.fit(X, y, GPSpec.create_vecchia(eps, 0.02, neighbors=32))
        r_v = rmse(v.mean_var(Xs)[0])
        globals_ = {
            "hermite": GPSpec.create(12, eps, noise=0.02),
            "rff_se": GPSpec.create_rff(eps, noise=0.02, num_features=256,
                                        seed=0),
            "rff_matern52": GPSpec.create_rff(
                eps, noise=0.02, kernel="matern52", num_features=256, seed=0
            ),
        }
        for name, spec in globals_.items():
            r_g = rmse(GP.fit(X, y, spec).mean_var(Xs)[0])
            assert r_v < r_g, f"vecchia {r_v:.4f} !< {name} {r_g:.4f}"

    def test_clustered_generator_contract(self):
        X, y, Xs, ys = make_clustered_dataset(300, seed=1)
        assert X.shape == (300, 2) and y.shape == (300,)
        assert Xs.shape == (30, 2) and ys.shape == (30,)
        # deterministic in seed
        X2, y2, *_ = make_clustered_dataset(300, seed=1)
        np.testing.assert_array_equal(np.asarray(X), np.asarray(X2))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


# ---------------------------------------------------------------------------
# 4. the Approximation protocol
# ---------------------------------------------------------------------------


class TestProtocol:
    def test_both_families_registered(self):
        assert available_approximations() == ["fagp", "vecchia"]
        assert get_approximation("vecchia") is vecchia.VECCHIA
        assert get_approximation("fagp").capabilities >= {
            "fit", "predict", "mean_var", "update", "nlml", "optimize",
        }

    def test_refusals_are_structured(self):
        X, y, Xs, _, spec = _vecchia_problem(N=60, k=8)
        g = GP.fit(X, y, spec)
        with pytest.raises(UnsupportedError, match="does not support") as ei:
            g.predict(Xs)
        assert (ei.value.layer, ei.value.capability) == (
            "approximation", "predict",
        )
        assert ei.value.spec is spec
        with pytest.raises(UnsupportedError, match="does not support") as ei:
            GP.optimize(X, y, spec)
        assert ei.value.capability == "optimize"
        with pytest.raises(UnsupportedError, match="n_features"):
            g.n_features

    def test_fagp_entry_points_refuse_vecchia_specs(self):
        """The module-level fagp functions run ONE family; a vecchia spec
        is bounced toward the facade with a structured error."""
        X, y, _, _, spec = _vecchia_problem(N=60, k=8)
        with pytest.raises(UnsupportedError, match="does not support") as ei:
            fagp.fit(X, y, spec)
        assert (ei.value.layer, ei.value.capability) == (
            "approximation", "fagp",
        )

    def test_bank_refuses_vecchia_specs(self):
        from repro.bank import GPBank

        _, _, _, _, spec = _vecchia_problem(N=60, k=8)
        with pytest.raises(UnsupportedError, match="does not support"):
            GPBank.create(spec, capacity=4)

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="kernel must be one of"):
            GPSpec.create_vecchia([0.8], 0.05, kernel="rbf")
        with pytest.raises(ValueError, match="neighbors >= 1"):
            GPSpec.create_vecchia([0.8], 0.05, neighbors=0)
        with pytest.raises(ValueError, match="unknown approximation"):
            GPSpec.create(6, eps=[0.8], approximation="svgp")

    def test_fit_input_validation(self):
        X, y, _, _, spec = _vecchia_problem(N=40, k=8)
        with pytest.raises(ValueError, match="p="):
            GP.fit(jnp.concatenate([X, X[:, :1]], axis=1), y, spec)
        with pytest.raises(ValueError, match="exceeds"):
            GP.fit(X[:4], y[:4], spec)  # k=8 > N=4

    def test_describe_names_the_family(self):
        _, _, _, _, spec = _vecchia_problem(k=24, kernel="matern52")
        d = spec.describe()
        assert "vecchia" in d and "matern52" in d and "24" in d


class TestSessionLifecycle:
    def test_update_equals_refit_exactly(self):
        """Vecchia's update is concatenation — the updated session is
        BIT-identical to a refit on the union (no approximation drift)."""
        X, y, _, _, spec = _vecchia_problem(N=80, k=12)
        Xn, yn, *_ = make_gp_dataset(20, 2, seed=7)
        up = GP.fit(X, y, spec).update(Xn, yn)
        re = GP.fit(jnp.concatenate([X, Xn]), jnp.concatenate([y, yn]), spec)
        Xs = _points(25, seed=8)
        np.testing.assert_array_equal(np.asarray(up.mean_var(Xs)[0]),
                                      np.asarray(re.mean_var(Xs)[0]))
        assert up.state.n_train == 100

    def test_update_task_mismatch_raises(self):
        X, y, _, _, spec = _vecchia_problem(N=40, k=8)
        g = GP.fit(X, jnp.stack([y, -y], axis=1), spec)
        with pytest.raises(ValueError, match="task"):
            g.update(X[:4], y[:4])

    def test_multioutput_matches_per_task(self):
        X, y, Xs, _, spec = _vecchia_problem(N=90, k=10)
        Y = jnp.stack([y, 2.0 * y, y - 0.5], axis=1)
        g = GP.fit(X, Y, spec)
        assert g.n_tasks == 3
        mu, var = g.mean_var(Xs)
        assert mu.shape == (Xs.shape[0], 3) and var.shape == (Xs.shape[0],)
        for t, yt in enumerate([y, 2.0 * y, y - 0.5]):
            mu_t, var_t = GP.fit(X, yt, spec).mean_var(Xs)
            np.testing.assert_allclose(np.asarray(mu[:, t]),
                                       np.asarray(mu_t), atol=1e-4)
            np.testing.assert_allclose(np.asarray(var), np.asarray(var_t),
                                       atol=1e-6)

    def test_checkpoint_roundtrip_bit_exact(self, tmp_path):
        X, y, Xs, _, spec = _vecchia_problem(N=70, k=9, kernel="matern52")
        g = GP.fit(X, y, spec)
        g.save(tmp_path)
        re = GP.load(tmp_path)
        assert isinstance(re.state, vecchia.VecchiaState)
        assert re.spec.approximation == "vecchia"
        assert re.spec.kernel == "matern52" and re.spec.neighbors == 9
        np.testing.assert_array_equal(np.asarray(re.state.X),
                                      np.asarray(g.state.X))
        np.testing.assert_array_equal(np.asarray(re.state.y),
                                      np.asarray(g.state.y))
        np.testing.assert_array_equal(np.asarray(re.mean_var(Xs)[0]),
                                      np.asarray(g.mean_var(Xs)[0]))

    def test_load_with_mismatched_spec_raises(self, tmp_path):
        X, y, _, _, spec = _vecchia_problem(N=50, k=6)
        GP.fit(X, y, spec).save(tmp_path)
        with pytest.raises(ValueError, match="mismatch"):
            GP.load(tmp_path, spec=spec.replace(neighbors=12))

    def test_with_spec_swaps_knobs_rejects_structure(self):
        X, y, _, _, spec = _vecchia_problem(N=50, k=6)
        g = GP.fit(X, y, spec)
        assert g.with_spec(block_rows=64).spec.block_rows == 64
        with pytest.raises(ValueError, match="mismatch"):
            g.with_spec(neighbors=12)
        with pytest.raises(ValueError, match="mismatch"):
            g.with_spec(kernel="matern52")
