"""FleetEngine battery: property-based interleavings, fault injection,
deadline semantics, bucket-shape pinning, latency metrics.

The serving pipeline's correctness claims, each pinned here:

* ANY interleaving of submit / observe / drain / ingest preserves the
  ticket -> result association and matches direct ``GPBank.mean_var`` /
  ``GPBank.update`` calls to <= 1e-5 (property-based via tests/hypcompat —
  real `hypothesis` when installed, fixed examples otherwise; both
  backends).
* A dispatch that raises mid-flight restores the router backlog in order
  and leaves the bank state bit-identical; every ticket is redeemable
  after the fault is repaired.
* A deadline-expired ticket yields the documented sentinel
  (``mu = NaN``, ``var = inf``, ``timed_out=True``) and never blocks or
  corrupts tickets behind it.
* Bucket autotuning never mints a new executable: the serving jit cache
  is warmed once per ladder rung and stays FIXED across arbitrary
  traffic/bucket churn.
* Engine percentiles are exactly ``numpy.percentile`` over the recorded
  samples.
"""
from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp
import pytest

from repro.bank import (
    BankRouter, FleetEngine, GPBank, LatencyStats, QueueFull,
    TIMEOUT_MU, TIMEOUT_VAR,
)
from repro.core import fagp
from repro.core.gp import GPSpec
from repro.data import make_gp_dataset

from hypcompat import given, settings, st  # hypothesis, or fixed examples


def _fleet(B, N, p, n, *, seed=0, backend="jnp"):
    spec = GPSpec.create(n, eps=[0.8] * p, rho=2.0, noise=0.05,
                         backend=backend)
    Xb = np.zeros((B, N, p), np.float32)
    yb = np.zeros((B, N), np.float32)
    for s in range(B):
        X, y, *_ = make_gp_dataset(N, p, seed=seed + s)
        Xb[s], yb[s] = np.asarray(X), np.asarray(y)
    return GPBank.fit(jnp.asarray(Xb), jnp.asarray(yb), spec)


def _engine(bank, *, microbatch=8, ingest_chunk=4, **kw):
    router = BankRouter(bank, microbatch=microbatch,
                        ingest_chunk=ingest_chunk)
    return FleetEngine(router, **kw), router


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------------------
# property-based interleavings vs a direct-call shadow model
# --------------------------------------------------------------------------


def _shadow_ingest(bank, queues, chunk):
    """Replicate BankRouter.ingest's decomposition with DIRECT
    ``GPBank.update`` calls: per-tenant chunks of ``chunk`` rows, padded +
    masked, distinct tenants per round."""
    p = bank.spec.p
    queues = {t: list(rows) for t, rows in queues.items() if rows}
    while queues:
        ids, Xg, yg, mg = [], [], [], []
        for t in list(queues):
            rows, rest = queues[t][:chunk], queues[t][chunk:]
            if rest:
                queues[t] = rest
            else:
                del queues[t]
            X = np.zeros((chunk, p), np.float32)
            y = np.zeros((chunk,), np.float32)
            m = np.zeros((chunk,), np.float32)
            for i, (x, yv) in enumerate(rows):
                X[i], y[i], m[i] = x, yv, 1.0
            ids.append(t)
            Xg.append(X)
            yg.append(y)
            mg.append(m)
        bank = bank.update(ids, jnp.asarray(np.stack(Xg)),
                           jnp.asarray(np.stack(yg)),
                           mask=jnp.asarray(np.stack(mg)))
    return bank


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
class TestInterleavingProperty:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 63),
           microbatch=st.sampled_from([3, 4, 8]),
           ingest_chunk=st.sampled_from([2, 5]))
    def test_any_interleaving_matches_direct_calls(
            self, backend, seed, microbatch, ingest_chunk):
        B, N, p, n = 4, 8, 2, 4
        bank = _fleet(B, N, p, n, backend=backend)
        shadow = bank
        eng, router = _engine(bank, microbatch=microbatch,
                              ingest_chunk=ingest_chunk)
        rng = np.random.default_rng(seed)

        sent = {}            # ticket -> (tenant, x)
        shadow_obs = {}      # tenant -> [(x, y)] not yet shadow-ingested
        got = {}             # ticket -> TicketResult
        expected = {}        # ticket -> (mu, var) from the shadow bank

        def do_drain():
            fresh = eng.drain()
            if fresh:
                ids = [sent[t][0] for t in fresh]
                X = np.stack([sent[t][1] for t in fresh])
                mu, var = shadow.mean_var(ids, jnp.asarray(X))
                mu, var = np.asarray(mu), np.asarray(var)
                for i, t in enumerate(fresh):
                    expected[t] = (mu[i], var[i])
            got.update(fresh)

        ops = rng.choice(["submit", "observe", "drain", "ingest"],
                         size=28, p=[0.55, 0.2, 0.15, 0.1])
        for op in ops:
            tenant = int(rng.integers(0, B))
            if op == "submit":
                x = rng.uniform(-1, 1, p).astype(np.float32)
                sent[eng.submit(tenant, x)] = (tenant, x)
            elif op == "observe":
                x = rng.uniform(-1, 1, p).astype(np.float32)
                y = float(rng.normal())
                eng.observe(tenant, x, y)
                shadow_obs.setdefault(tenant, []).append((x, y))
            elif op == "drain":
                do_drain()
            else:  # ingest: results already in flight belong to the OLD
                # bank, so the pipeline is drained first (same barrier the
                # serving loop uses between rounds)
                do_drain()
                eng.ingest()
                shadow = _shadow_ingest(shadow, shadow_obs, ingest_chunk)
                shadow_obs = {}
        do_drain()
        eng.ingest()

        # every ticket answered exactly once, against its own submission
        assert set(got) == set(sent)
        for t, r in got.items():
            assert r.ok
            mu_s, var_s = expected[t]
            assert abs(r.mu - mu_s) <= 1e-5, (t, r.mu, mu_s)
            assert abs(r.var - var_s) <= 1e-5, (t, r.var, var_s)


# --------------------------------------------------------------------------
# fault injection
# --------------------------------------------------------------------------


class TestFaultInjection:
    def test_dispatch_failure_restores_backlog_and_bank(self):
        bank = _fleet(4, 8, 2, 4)
        eng, router = _engine(bank, auto_pump=False)
        tks = [eng.submit(i % 4, np.full(2, 0.1 * i, np.float32))
               for i in range(6)]
        before = [(t, x.copy()) for _, t, x in router._pending]
        stack0 = {f: np.asarray(getattr(router.bank.stack, f)).copy()
                  for f in ("chol", "u", "b", "lam", "sqrtlam")}

        real = eng._dispatch
        calls = []

        def boom(entries, bucket):
            calls.append(len(entries))
            raise RuntimeError("injected mid-flight fault")

        eng._dispatch = boom
        with pytest.raises(RuntimeError, match="injected"):
            eng.pump()
        # backlog restored in arrival order, bank bit-identical
        assert [(t, tuple(x)) for _, t, x in router._pending] \
            == [(t, tuple(x)) for t, x in before]
        for f, v in stack0.items():
            assert np.array_equal(
                np.asarray(getattr(router.bank.stack, f)), v
            ), f
        assert eng.in_flight_blocks == 0 and eng.in_flight_rows == 0

        # after repair every ticket is still redeemable
        eng._dispatch = real
        out = eng.drain()
        assert set(out) == set(tks) and all(out[t].ok for t in tks)

    def test_failed_ingest_restores_queue_and_serving_continues(self):
        bank = _fleet(4, 8, 2, 4)
        eng, router = _engine(bank)
        eng.observe(1, np.zeros(2, np.float32), 0.5)
        orig = router.bank
        # swap in a bank that has never seen tenant 1: ingest must fail,
        # restore the observation queue, and succeed after repair
        router.bank = GPBank.create(orig.spec, capacity=orig.capacity)
        with pytest.raises(KeyError):
            eng.ingest()
        assert router._observations[1], "queued observation was dropped"
        router.bank = orig
        assert eng.ingest() == 1
        t = eng.submit(1, np.zeros(2, np.float32))
        assert eng.drain()[t].ok

    def test_expired_ticket_never_blocks_later_tickets(self):
        clock = _FakeClock()
        bank = _fleet(4, 8, 2, 4)
        eng, router = _engine(bank, auto_pump=False, clock=clock)
        doomed = eng.submit(0, np.zeros(2, np.float32), deadline_s=1.0)
        clock.t = 0.5
        live1 = eng.submit(1, np.ones(2, np.float32))
        clock.t = 2.0  # doomed expired, live1 has no deadline
        live2 = eng.submit(2, np.full(2, -0.5, np.float32), deadline_s=10.0)
        out = eng.drain()
        assert out[doomed].timed_out
        assert math.isnan(out[doomed].mu) and out[doomed].var == TIMEOUT_VAR
        assert math.isnan(TIMEOUT_MU) and TIMEOUT_VAR == float("inf")
        assert out[live1].ok and out[live2].ok
        assert np.isfinite(out[live1].mu) and np.isfinite(out[live2].mu)
        # the sentinel is recorded as a timeout, not a completion
        m = eng.metrics()
        assert m["overall"]["expired"] == 1
        assert m["overall"]["completed"] == 2
        assert m["tenants"][0]["timeouts"] == 1

    def test_default_slo_expires_whole_burst_then_serves_it(self):
        """A burst under an impossible default SLO (1 ns) comes back as
        the timeout sentinel, every ticket; the same rows submitted again
        with a live deadline are then all served, none dropped, each
        matching a direct ``GPBank.mean_var``."""
        clock = _FakeClock()
        bank = _fleet(8, 8, 2, 4)
        eng, _ = _engine(bank, auto_pump=False, clock=clock,
                         default_slo_s=1e-9, max_coalesce=4)
        rng = np.random.default_rng(7)
        nq = 64
        tenants = [int(t) for t in rng.integers(0, 8, nq)]
        Xq = rng.uniform(-1, 1, (nq, 2)).astype(np.float32)
        doomed = [eng.submit(t, x) for t, x in zip(tenants, Xq)]
        clock.t = 0.002                      # every deadline has lapsed
        out = eng.drain()
        assert set(out) == set(doomed)
        assert all(out[t].timed_out for t in doomed)
        assert all(math.isnan(out[t].mu) and out[t].var == TIMEOUT_VAR
                   for t in doomed)
        live = [eng.submit(t, x, deadline_s=60.0)
                for t, x in zip(tenants, Xq)]
        out = eng.drain()
        assert set(out) == set(live) and all(out[t].ok for t in live)
        mu, var = bank.mean_var(tenants, jnp.asarray(Xq))
        np.testing.assert_allclose([out[t].mu for t in live],
                                   np.asarray(mu), atol=1e-5, rtol=0)
        np.testing.assert_allclose([out[t].var for t in live],
                                   np.asarray(var), atol=1e-5, rtol=0)
        m = eng.metrics()["overall"]
        assert m["expired"] == nq and m["completed"] == nq

    def test_queue_budget_backpressure(self):
        bank = _fleet(4, 8, 2, 4)
        eng, _ = _engine(bank, queue_budget=3, auto_pump=False)
        for i in range(3):
            eng.submit(0, np.zeros(2, np.float32))
        with pytest.raises(QueueFull):
            eng.submit(0, np.zeros(2, np.float32))
        # draining frees the budget
        eng.drain()
        assert eng.depth == 0
        eng.submit(0, np.zeros(2, np.float32))


# --------------------------------------------------------------------------
# bucket autotuning: shapes are pinned, churn mints no executables
# --------------------------------------------------------------------------


class TestBucketShapes:
    def test_ladder_is_fixed_powers_of_two(self):
        from repro.bank.engine import _pow2_buckets
        assert _pow2_buckets(8) == (1, 2, 4, 8)
        assert _pow2_buckets(8, 4) == (1, 2, 4, 8, 16, 32)
        assert _pow2_buckets(1, 1) == (1,)
        assert _pow2_buckets(64, 4) == (1, 2, 4, 8, 16, 32, 64, 128, 256)

    def test_backlog_coalesces_up_the_ladder(self):
        bank = _fleet(4, 8, 2, 4)
        eng, _ = _engine(bank, microbatch=4, auto_pump=False,
                         max_coalesce=4)
        for i in range(11):
            eng.submit(i % 4, np.full(2, 0.05 * i, np.float32))
        eng.pump(max_blocks=1)
        # 11 pending -> one padded 16-row block, not three 4-row blocks
        assert eng.bucket_uses == {16: 1}
        out = eng.drain()
        assert len(out) == 11

    def test_traffic_churn_mints_no_new_executables(self):
        bank = _fleet(4, 8, 2, 4)
        eng, _ = _engine(bank, microbatch=8, auto_pump=False,
                         max_coalesce=2)
        rng = np.random.default_rng(0)
        # warm every rung of the ladder once
        for rung in eng.buckets:
            for i in range(rung):
                eng.submit(int(rng.integers(0, 4)),
                           rng.uniform(-1, 1, 2).astype(np.float32))
            eng.pump(max_blocks=1)
            eng.drain()
        serve0 = fagp._bank_gathered_posterior._cache_size()
        # arbitrary churn: every dispatch reuses a warmed rung
        for _ in range(12):
            for _ in range(int(rng.integers(1, 17))):
                eng.submit(int(rng.integers(0, 4)),
                           rng.uniform(-1, 1, 2).astype(np.float32))
            eng.drain()
        assert fagp._bank_gathered_posterior._cache_size() == serve0

    def test_ingest_donation_matches_non_donated(self):
        bank = _fleet(4, 8, 2, 4)
        rng = np.random.default_rng(3)
        rows = [(int(rng.integers(0, 4)),
                 rng.uniform(-1, 1, 2).astype(np.float32),
                 float(rng.normal())) for _ in range(6)]
        plain = BankRouter(bank, microbatch=8, ingest_chunk=4)
        donated = BankRouter(bank, microbatch=8, ingest_chunk=4,
                             donate_updates=True)
        for router in (plain, donated):
            for t, x, y in rows:
                router.observe(t, x, y)
            assert router.ingest() == 6
        xq = np.full(2, 0.2, np.float32)
        for t in range(4):
            mu_a, var_a = plain.bank.mean_var([t], jnp.asarray(xq[None]))
            mu_b, var_b = donated.bank.mean_var([t], jnp.asarray(xq[None]))
            assert abs(float(mu_a[0]) - float(mu_b[0])) <= 1e-6
            assert abs(float(var_a[0]) - float(var_b[0])) <= 1e-6


# --------------------------------------------------------------------------
# latency metrics: numpy.percentile reference semantics
# --------------------------------------------------------------------------


class TestLatencyMetrics:
    def test_percentiles_match_numpy_reference(self):
        rng = np.random.default_rng(11)
        stats = LatencyStats()
        ref = {}
        for tenant in range(3):
            samples = rng.exponential(0.01, size=rng.integers(5, 40))
            for s in samples:
                stats.record(tenant, float(s))
            ref[tenant] = samples
        for tenant, samples in ref.items():
            p50, p99 = stats.percentiles(tenant)
            assert p50 == pytest.approx(
                float(np.percentile(samples, 50)), abs=0, rel=0)
            assert p99 == pytest.approx(
                float(np.percentile(samples, 99)), abs=0, rel=0)
        pooled = np.concatenate(list(ref.values()))
        p50, p99 = stats.percentiles(None)
        assert p50 == float(np.percentile(pooled, 50))
        assert p99 == float(np.percentile(pooled, 99))
        assert all(math.isnan(v) for v in stats.percentiles("nobody"))

    def test_engine_metrics_are_percentiles_of_recorded_samples(self):
        bank = _fleet(4, 8, 2, 4)
        eng, _ = _engine(bank)
        rng = np.random.default_rng(5)
        tks = [eng.submit(int(rng.integers(0, 4)),
                          rng.uniform(-1, 1, 2).astype(np.float32))
               for _ in range(40)]
        out = eng.drain()
        assert all(out[t].ok for t in tks)
        m = eng.metrics()
        pooled = [s for lst in eng.stats.samples.values() for s in lst]
        assert m["overall"]["p50_s"] == float(np.percentile(pooled, 50))
        assert m["overall"]["p99_s"] == float(np.percentile(pooled, 99))
        assert m["overall"]["completed"] == 40
        assert sum(v["count"] for v in m["tenants"].values()) == 40
        # every completed ticket carried its own latency
        assert all(out[t].latency_s >= 0.0 for t in tks)
        assert m["overall"]["sustained_qps"] > 0
