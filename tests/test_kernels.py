"""Per-kernel allclose tests: Pallas (interpret mode on CPU) vs pure-jnp ref.

Sweeps shapes (aligned + ragged) and dtypes, plus hypothesis property tests,
plus cross-validation of the kernel path against core.mercer (two independent
implementations of paper Eq. 19).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st  # hypothesis, or fixed examples

from repro.core import mercer
from repro.kernels import ops, ref


def _setup(N, p, n_max, kind="full", degree=None, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(-2, 2, size=(N, p)).astype(np.float32))
    eps = jnp.asarray(rng.uniform(0.3, 1.2, size=(p,)).astype(np.float32))
    rho = jnp.asarray(rng.uniform(1.5, 3.0, size=(p,)).astype(np.float32))
    idx = mercer.make_index_set(kind, n_max, p, degree)
    consts = ref.phi_consts(eps, rho)
    S = jnp.asarray(ref.one_hot_selection(idx, n_max))
    return X, eps, rho, idx, consts, S


class TestResolveInterpret:
    @pytest.mark.parametrize("backend,expected", [("cpu", True),
                                                  ("tpu", False)])
    def test_default_follows_backend(self, monkeypatch, backend, expected):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert ops.resolve_interpret(None) is expected

    def test_other_backends_are_refused(self, monkeypatch):
        """No silent interpret fallback on an accelerator the kernels are
        not written for."""
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.resolve_interpret(None)

    def test_explicit_choice_wins(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        assert ops.resolve_interpret(True) is True
        assert ops.resolve_interpret(False) is False


class TestHermitePhi:
    @pytest.mark.parametrize(
        "N,p,n_max",
        [
            (8, 1, 1),      # degenerate: single eigenvalue
            (64, 1, 8),
            (100, 2, 6),    # ragged N
            (256, 3, 5),
            (300, 4, 4),    # ragged, multi-dim
            (512, 2, 33),   # n_max past any small unroll assumptions
        ],
    )
    def test_matches_ref(self, N, p, n_max):
        X, eps, rho, idx, consts, S = _setup(N, p, n_max)
        out = ops.hermite_phi(X, consts, S, n_max=n_max)
        expect = ref.ref_phi(X.T, consts, S, n_max)
        assert out.shape == (N, idx.shape[0])
        # rtol scales with recurrence depth: two independent f32 recurrences
        # accumulate ~ULP/step of drift in the pre-envelope magnitudes
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), rtol=4e-5 * max(4, n_max), atol=1e-5
        )

    def test_matches_core_mercer(self):
        """Kernel path == core/mercer.phi_nd (independent scan-based impl)."""
        N, p, n_max = 128, 3, 6
        X, eps, rho, idx, consts, S = _setup(N, p, n_max)
        params = mercer.SEKernelParams.create(eps, rho)
        out = ops.hermite_phi(X, consts, S, n_max=n_max)
        expect = mercer.phi_nd(X, jnp.asarray(idx), params, n_max)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-5)

    def test_truncated_index_set(self):
        N, p, n_max = 96, 3, 6
        X, eps, rho, idx, consts, S = _setup(N, p, n_max, kind="hyperbolic_cross", degree=8)
        out = ops.hermite_phi(X, consts, S, n_max=n_max)
        expect = ref.ref_phi(X.T, consts, S, n_max)
        assert out.shape[1] == idx.shape[0] < n_max**p
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-4, atol=1e-5)

    @given(
        N=st.integers(1, 130),
        p=st.integers(1, 3),
        n_max=st.integers(1, 9),
        seed=st.integers(0, 99),
    )
    @settings(max_examples=12, deadline=None)
    def test_property_random_shapes(self, N, p, n_max, seed):
        X, eps, rho, idx, consts, S = _setup(N, p, n_max, seed=seed)
        out = ops.hermite_phi(X, consts, S, n_max=n_max)
        expect = ref.ref_phi(X.T, consts, S, n_max)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-4, atol=1e-5)


class TestScaledGram:
    @pytest.mark.parametrize(
        "N,M", [(64, 16), (512, 128), (300, 100), (1024, 256), (100, 257)]
    )
    def test_matches_ref(self, N, M):
        rng = np.random.default_rng(1)
        Phi = jnp.asarray(rng.standard_normal((N, M)).astype(np.float32))
        d = jnp.asarray(np.geomspace(1.0, 1e-6, M).astype(np.float32))
        sig2 = jnp.float32(0.01)
        out = ops.scaled_gram(Phi, d, sig2)
        expect = ref.ref_scaled_gram(Phi, d, sig2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(2)
        Phi = jnp.asarray(rng.standard_normal((256, 64)).astype(np.float32)).astype(dtype)
        d = jnp.ones((64,), jnp.float32)
        sig2 = jnp.float32(0.5)
        out = ops.scaled_gram(Phi, d, sig2)
        expect = ref.ref_scaled_gram(Phi.astype(jnp.float32), d, sig2)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        assert out.dtype == jnp.float32  # f32 accumulation regardless of input
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=tol, atol=tol)

    def test_spd(self):
        rng = np.random.default_rng(3)
        Phi = jnp.asarray(rng.standard_normal((512, 96)).astype(np.float32))
        d = jnp.asarray(np.geomspace(1, 1e-4, 96).astype(np.float32))
        out = np.asarray(ops.scaled_gram(Phi, d, jnp.float32(0.1)))
        np.testing.assert_allclose(out, out.T, atol=1e-5)
        assert np.linalg.eigvalsh(out).min() >= 0.99  # >= I by construction


class TestDiagQuad:
    @pytest.mark.parametrize("N,M", [(64, 32), (256, 128), (100, 60), (513, 256)])
    def test_matches_ref(self, N, M):
        rng = np.random.default_rng(4)
        A = jnp.asarray(rng.standard_normal((N, M)).astype(np.float32))
        C0 = rng.standard_normal((M, M)).astype(np.float32)
        C = jnp.asarray(C0 @ C0.T / M)
        out = ops.diag_quad(A, C)
        expect = ref.ref_diag_quad(A, C)
        assert out.shape == (N,)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-4, atol=2e-4)

    @given(N=st.integers(1, 70), M=st.integers(1, 40), seed=st.integers(0, 99))
    @settings(max_examples=10, deadline=None)
    def test_property_random_shapes(self, N, M, seed):
        rng = np.random.default_rng(seed)
        A = jnp.asarray(rng.standard_normal((N, M)).astype(np.float32))
        C = jnp.asarray(rng.standard_normal((M, M)).astype(np.float32))
        out = ops.diag_quad(A, C)
        expect = ref.ref_diag_quad(A, C)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=3e-4, atol=3e-4)


class TestEndToEndKernelFAGP:
    def test_kernel_pipeline_matches_dense_posterior(self):
        """Full kernel pipeline (phi -> gram -> solve -> diag_quad) reproduces
        the core FAGP posterior mean/variance."""
        from repro.core import fagp

        N, Ns, p, n_max = 200, 40, 2, 8
        X, eps, rho, idx, consts, S = _setup(N, p, n_max)
        Xs, *_ = _setup(Ns, p, n_max, seed=9)
        rng = np.random.default_rng(5)
        y = jnp.asarray(
            (np.sum(np.cos(np.asarray(X)), axis=1) + 0.05 * rng.standard_normal(N)).astype(np.float32)
        )
        params = mercer.SEKernelParams.create(eps, rho, noise=0.05)
        spec = fagp.GPSpec.create(n_max, eps=params.eps, rho=params.rho, noise=0.05)
        st_ = fagp.fit(X, y, spec)
        mu_ref, cov_ref = fagp.predict(st_, Xs)

        # kernel pipeline
        Phi = ops.hermite_phi(X, consts, S, n_max=n_max)
        sig2 = params.noise**2
        B = ops.scaled_gram(Phi, st_.sqrtlam, sig2)
        chol = jnp.linalg.cholesky(B)
        b = Phi.T @ y
        u = st_.sqrtlam * jax.scipy.linalg.cho_solve((chol, True), st_.sqrtlam * b) / sig2
        Phis = ops.hermite_phi(Xs, consts, S, n_max=n_max)
        mu = Phis @ u
        Binv = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(B.shape[0]))
        var = ops.diag_quad(Phis * st_.sqrtlam[None, :], Binv)
        np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_ref), rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(var), np.diag(np.asarray(cov_ref)), rtol=2e-3, atol=1e-5
        )
