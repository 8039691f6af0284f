"""The blocked triangular solves behind serving and the fit's mean weights.

``fagp._forward_blocks`` (V = L^{-1} R) and ``fagp._backward_blocks``
(X = L^{-T} Y) substitute block by block over ``chol_dinv``, the inverses
of L's diagonal blocks that the fit prepares once.  Pinned here on the CPU
against ``jax.scipy.linalg.solve_triangular`` at f32: every block size the
code can choose, ragged and whole-block M, one to many columns; and the
state plumbing: a fitted, updated or checkpoint-loaded state carries the
blocks, a bank or distributed state does not and serves through the
in-call fallback, with the same answers either way.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bank import GPBank
from repro.checkpoint import gpstate
from repro.core import fagp

# f32 substitution on a factor with condition number ~2: errors are a few
# ulps of the largest entry, so 1e-5 of it leaves room and catches any
# misplaced block (which is off by O(1))
RTOL = 1e-5
BLOCKS = (128, 256, 512)          # every TB that _solve_block returns


def _factor(M, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((M, M)).astype(np.float32)
    B = np.eye(M, dtype=np.float32) + A @ A.T / M
    return jnp.linalg.cholesky(jnp.asarray(B))


def _rhs(M, Q, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((M, Q)).astype(np.float32))


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = np.max(np.abs(want))
    np.testing.assert_allclose(got, want, rtol=0, atol=rtol * scale)


def _data(N, p, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, (N, p)).astype(np.float32)
    y = np.sum(np.cos(X), axis=1) + 0.05 * rng.standard_normal(N)
    return jnp.asarray(X), jnp.asarray(y.astype(np.float32))


def _spec(p, n, **kw):
    return fagp.GPSpec.create(n, eps=[0.8] * p, rho=2.0, noise=0.05, **kw)


@pytest.mark.parametrize("tb", BLOCKS)
@pytest.mark.parametrize("Q", [1, 7, 256])
@pytest.mark.parametrize("M", [64, 128, 300, 1000])
def test_forward_blocks_match_solve_triangular(M, Q, tb):
    L, R = _factor(M), _rhs(M, Q)
    dinv = fagp._diag_block_inverses(L, min(tb, M))
    want = jax.scipy.linalg.solve_triangular(L, R, lower=True)
    V, ss = fagp._forward_blocks(L, dinv, R)
    _close(V, want)
    _close(ss, jnp.sum(want * want, axis=0))


@pytest.mark.parametrize("tb", BLOCKS)
@pytest.mark.parametrize("Q", [1, 7, 256])
@pytest.mark.parametrize("M", [64, 128, 300, 1000])
def test_backward_blocks_match_solve_triangular(M, Q, tb):
    L, Y = _factor(M), _rhs(M, Q)
    dinv = fagp._diag_block_inverses(L, min(tb, M))
    want = jax.scipy.linalg.solve_triangular(L, Y, lower=True, trans=1)
    _close(fagp._backward_blocks(L, dinv, Y), want)


@pytest.mark.parametrize("M", [64, 300, 1000])
def test_diag_block_inverses_pad_the_ragged_block_with_identity(M):
    L = _factor(M)
    tb = 128
    dinv = fagp._diag_block_inverses(L, tb)
    nb = -(-M // tb)
    assert dinv.shape == (nb, tb, tb)
    w = M - (nb - 1) * tb
    last = L[(nb - 1) * tb:, (nb - 1) * tb:]
    _close(dinv[-1, :w, :w] @ last, jnp.eye(w), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(dinv[-1, w:, w:]), np.eye(tb - w))
    np.testing.assert_array_equal(np.asarray(dinv[-1, w:, :w]), 0.0)


@pytest.mark.parametrize("tb", BLOCKS)
@pytest.mark.parametrize("shape", [(300,), (300, 4), (1000,), (1000, 3)],
                         ids=lambda s: "x".join(map(str, s)))
def test_blocked_mean_weights_match_cho_solve(shape, tb):
    """_factor's u (single- and multi-task) against the cho_solve that the
    bank and the distributed fit keep."""
    M = shape[0]
    L = _factor(M)
    rng = np.random.default_rng(2)
    b = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    d = jnp.asarray(rng.uniform(0.1, 1.0, M).astype(np.float32))
    sig2 = jnp.float32(0.05) ** 2
    dinv = fagp._diag_block_inverses(L, min(tb, M))
    _close(fagp._blocked_mean_weights(L, dinv, d, b, sig2),
           fagp._solve_mean_weights(L, d, b, sig2))


def test_solve_block_is_one_of_the_tested_sizes():
    for M in (1, 36, 64, 128, 300, 1000, 1024, 4096, 14641, 50000):
        tb = fagp._solve_block(M)
        assert tb in {min(M, t) for t in BLOCKS}, (M, tb)
    assert fagp._solve_block(14641) == 512


# -- state plumbing ----------------------------------------------------------


def _relclose(a, b, rtol=1e-6):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    np.testing.assert_allclose(a, b, rtol=rtol, atol=rtol * np.max(np.abs(b)))


@pytest.fixture(scope="module", params=["jnp", "pallas"])
def fitted(request):
    """A fitted state (p = 2, n = 12: M = 144, two blocks of 128) and
    query rows."""
    X, y = _data(96, 2)
    Xs, _ = _data(40, 2, seed=5)
    return fagp.fit(X, y, _spec(2, 12, backend=request.param)), Xs


def test_fit_prepares_the_factor(fitted):
    st, _ = fitted
    assert st.chol_dinv is not None
    _relclose(st.chol_dinv, fagp._prepare_factor(st.chol))


def test_prepared_and_unprepared_states_agree(fitted):
    st, Xs = fitted
    bare = dataclasses.replace(st, chol_dinv=None)
    for a, b in zip(fagp.predict_mean_var(st, Xs),
                    fagp.predict_mean_var(bare, Xs)):
        _relclose(a, b)


def test_with_spec_carries_the_blocks(fitted):
    st, _ = fitted
    other = "pallas" if st.spec.backend == "jnp" else "jnp"
    assert st.with_spec(backend=other).chol_dinv is st.chol_dinv


def test_fit_update_reprepares_the_factor(fitted):
    st, Xs = fitted
    Xn, yn = _data(8, 2, seed=9)
    up = fagp.fit_update(st, Xn, yn)
    assert not np.allclose(np.asarray(up.chol), np.asarray(st.chol))
    _relclose(up.chol_dinv, fagp._prepare_factor(up.chol))
    for a, b in zip(fagp.predict_mean_var(up, Xs),
                    fagp.predict_mean_var(
                        dataclasses.replace(up, chol_dinv=None), Xs)):
        _relclose(a, b)


def test_checkpoint_round_trip_serves(fitted, tmp_path):
    st, Xs = fitted
    gpstate.save_state(tmp_path / "ckpt", st)
    _, back, _ = gpstate.load_state(tmp_path / "ckpt")
    assert back.chol_dinv is not None
    for a, b in zip(fagp.predict_mean_var(back, Xs),
                    fagp.predict_mean_var(st, Xs)):
        _relclose(a, b)


def test_bank_state_serves_through_the_fallback():
    """A tenant's state unstacked from a bank carries no prepared blocks;
    it serves what the same fit served alone (the bank's own moments and
    cho_solve weights differ in f32 rounding alone: 1e-5, as the bank's
    parity tests)."""
    p, n = 2, 6
    Xs, _ = _data(24, p, seed=5)
    data = [_data(48, p, seed=s) for s in (1, 2)]
    spec = _spec(p, n)
    bank = GPBank.fit(jnp.stack([X for X, _ in data]),
                      jnp.stack([y for _, y in data]), spec)
    for t, (X, y) in enumerate(data):
        st = bank.state(t)
        assert st.chol_dinv is None
        alone = fagp.fit(X, y, spec)
        for a, b in zip(fagp.predict_mean_var(st, Xs),
                        fagp.predict_mean_var(alone, Xs)):
            _relclose(a, b, rtol=1e-5)
