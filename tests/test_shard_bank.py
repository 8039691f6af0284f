"""Sharded mega-bank tests on 8 virtual devices (subprocess-isolated:
XLA device count is locked at first jax init, so each test body runs in
its own python with XLA_FLAGS=--xla_force_host_platform_device_count=8).

Covers the ShardedGPBank contract: sharded-vs-resident serving parity on
both backends, cross-shard insert/evict/rebalance churn with the jit
cache-miss pin (zero new executables per shard once the shape ladder is
warm), deterministic placement (round-robin fit, least-loaded insert,
fullest-donor rebalance), the 2-D (bank, data) mesh composition with the
v2 row-sharded fit, and the router/engine/tiered integration."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared subprocess preamble: a 16-tenant fleet, a resident bank, and its
# 4-shard twin serving the identical states
FLEET = """
    import jax, numpy as np, jax.numpy as jnp
    from repro.bank import GPBank, ShardedGPBank
    from repro.core.gp import GPSpec
    from repro.data import make_gp_dataset
    from repro.launch.mesh import make_bank_mesh

    B, N_ROWS, P, S = 16, 8, 2, 4
    BACKEND = {backend!r}
    spec = GPSpec.create(8, eps=[0.8] * P, rho=2.0, noise=0.05,
                         backend=BACKEND)
    Xb = np.zeros((B, N_ROWS, P), np.float32)
    yb = np.zeros((B, N_ROWS), np.float32)
    for s in range(B):
        X, y, *_ = make_gp_dataset(N_ROWS, P, seed=s)
        Xb[s], yb[s] = np.asarray(X), np.asarray(y)
    Xb, yb = jnp.asarray(Xb), jnp.asarray(yb)
    rng = np.random.default_rng(0)
    nq = 64
    Xq = jnp.asarray(rng.uniform(-1, 1, size=(nq, P)).astype(np.float32))
    tenants = [int(t) for t in rng.integers(0, B, nq)]

    mesh = make_bank_mesh(S)
    resident = GPBank.fit(Xb, yb, spec)
    sharded = ShardedGPBank.from_bank(resident, mesh)
"""


def run_sub(body: str, *, backend: str = "jnp", timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    src = textwrap.dedent(FLEET).format(backend=backend) \
        + textwrap.dedent(body)
    r = subprocess.run(
        [sys.executable, "-c", src],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


class TestShardedParity:
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_fit_mean_var_update_match_resident(self, backend):
        run_sub("""
            # serving the SAME states: sharded answers must match the
            # resident bank's to f32 noise
            mu_r, var_r = resident.mean_var(tenants, Xq)
            mu_s, var_s = sharded.mean_var(tenants, Xq)
            np.testing.assert_allclose(np.asarray(mu_s), np.asarray(mu_r),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(var_s), np.asarray(var_r),
                                       atol=1e-5)

            # an independent sharded FIT of the same data serves the same
            # posterior; the fit is a different lowering of the same
            # moments (B/S vs B leading dim changes XLA's f32 reduction
            # order), so agreement is looser than the exact serving parity
            fitted = ShardedGPBank.fit(Xb, yb, spec, mesh)
            mu_f, var_f = fitted.mean_var(tenants, Xq)
            np.testing.assert_allclose(np.asarray(mu_f), np.asarray(mu_r),
                                       rtol=0, atol=1e-4)
            np.testing.assert_allclose(np.asarray(var_f), np.asarray(var_r),
                                       rtol=0, atol=1e-4)

            # rank-k update on a mixed-tenant batch tracks the resident
            # update (pallas interpret kernels round differently per
            # scatter-group shape, so that backend gets f32 headroom)
            upd = [0, 3, 7, 12]
            Xk = jnp.asarray(rng.uniform(-1, 1, (len(upd), 2, 2))
                             .astype(np.float32))
            yk = jnp.asarray(rng.normal(size=(len(upd), 2))
                             .astype(np.float32))
            res2 = resident.update(upd, Xk, yk)
            sh2 = sharded.update(upd, Xk, yk)
            mu_r2, _ = res2.mean_var(tenants, Xq)
            mu_s2, _ = sh2.mean_var(tenants, Xq)
            atol = 1e-5 if BACKEND == "jnp" else 1e-4
            np.testing.assert_allclose(np.asarray(mu_s2),
                                       np.asarray(mu_r2), rtol=0, atol=atol)

            # round-trip: to_bank() hands back a resident bank with
            # identical answers
            back = sharded.to_bank()
            mu_b, _ = back.mean_var(tenants, Xq)
            np.testing.assert_allclose(np.asarray(mu_b), np.asarray(mu_r),
                                       atol=1e-5)
        """, backend=backend)

    def test_eight_shard_fit_agreement(self):
        run_sub("""
            # a 1024-tenant fleet across all 8 devices: a sharded FIT of
            # the same data agrees with the resident fit to 5e-5 (only the
            # f32 summation order differs), and serving the resident
            # states sharded matches the resident bank and a loop of
            # single-model sessions (over 64 tenants) to 1e-5
            from repro.core.gp import GP

            B = 1024
            Xb = np.zeros((B, N_ROWS, P), np.float32)
            yb = np.zeros((B, N_ROWS), np.float32)
            for s in range(B):
                X, y, *_ = make_gp_dataset(N_ROWS, P, seed=s)
                Xb[s], yb[s] = np.asarray(X), np.asarray(y)
            Xb, yb = jnp.asarray(Xb), jnp.asarray(yb)
            nq = 512
            Xq = jnp.asarray(rng.uniform(-1, 1, (nq, P)).astype(np.float32))
            tenants = np.asarray(rng.integers(0, B, nq))
            ids = [int(t) for t in tenants]
            mesh8 = make_bank_mesh(8)
            resident = GPBank.fit(Xb, yb, spec)
            mu_r, var_r = resident.mean_var(ids, Xq)

            fitted = ShardedGPBank.fit(Xb, yb, spec, mesh8)
            mu_f, var_f = fitted.mean_var(ids, Xq)
            np.testing.assert_allclose(np.asarray(mu_f), np.asarray(mu_r),
                                       rtol=0, atol=5e-5)
            np.testing.assert_allclose(np.asarray(var_f), np.asarray(var_r),
                                       rtol=0, atol=5e-5)

            sharded = ShardedGPBank.from_bank(resident, mesh8)
            mu_s, var_s = sharded.mean_var(ids, Xq)
            np.testing.assert_allclose(np.asarray(mu_s), np.asarray(mu_r),
                                       rtol=0, atol=1e-5)
            np.testing.assert_allclose(np.asarray(var_s), np.asarray(var_r),
                                       rtol=0, atol=1e-5)
            mu_s, var_s = np.asarray(mu_s), np.asarray(var_s)
            for t in range(64):
                rows = np.flatnonzero(tenants == t)
                if rows.size == 0:
                    continue
                m1, v1 = GP.from_state(sharded.state(t)).mean_var(
                    Xq[jnp.asarray(rows)])
                np.testing.assert_allclose(mu_s[rows], np.asarray(m1),
                                           rtol=0, atol=1e-5)
                np.testing.assert_allclose(var_s[rows], np.asarray(v1),
                                           rtol=0, atol=1e-5)
        """)

    def test_2d_bank_data_mesh_fit(self):
        run_sub("""
            # (bank, data) mesh: the fit row-shards each shard's N axis
            # (one psum over 'data'), serving stays bank-only
            mesh2 = make_bank_mesh(4, 2)
            fitted = ShardedGPBank.fit(Xb, yb, spec, mesh2)
            mu_r, var_r = resident.mean_var(tenants, Xq)
            mu_f, var_f = fitted.mean_var(tenants, Xq)
            # row-sharding splits each tenant's moment sums across the
            # 'data' axis (psum changes the f32 summation order feeding
            # the solve), so the fit agreement is looser than the exact
            # 1-D serving parity
            np.testing.assert_allclose(np.asarray(mu_f), np.asarray(mu_r),
                                       rtol=0, atol=1e-4)
            np.testing.assert_allclose(np.asarray(var_f), np.asarray(var_r),
                                       rtol=0, atol=1e-4)
        """)

    def test_homogeneous_only_and_capacity_guards(self):
        run_sub("""
            import dataclasses, pytest
            het = dataclasses.replace(resident,
                                      hypers=resident._stacked_hypers())
            try:
                ShardedGPBank.from_bank(het, mesh)
            except ValueError as e:
                assert "heterogeneous" in str(e)
            else:
                raise AssertionError("hetero bank must be rejected")
            try:
                ShardedGPBank.create(spec, 10, mesh)   # not a multiple of S
            except ValueError as e:
                assert "multiple" in str(e)
            else:
                raise AssertionError("capacity % S != 0 must be rejected")
        """)


class TestShardedChurn:
    def test_insert_evict_rebalance_zero_recompiles(self):
        run_sub("""
            from repro.bank import sharded as sh_mod

            def churn_cycle(bank, tag):
                # evict two tenants off shard 0, insert two fresh ones
                # (least-loaded placement routes them back), rebalance,
                # then serve + read a state — the full churn surface
                victims = [t for t in bank.tenants
                           if bank.shard_of(t) == 0][:2]
                for t in victims:
                    st = bank.state(t)
                    bank = bank.evict(t)
                for i, t in enumerate(victims):
                    bank = bank.insert((tag, i), st)
                bank, moves = bank.rebalance()
                tl = list(bank.tenants)      # every tenant exactly once:
                mu, var = bank.mean_var(tl, Xq[:len(tl)])
                jax.block_until_ready(mu)
                bank.state(bank.tenants[0])
                return bank

            # warm: one full cycle compiles the shape ladder (per-shard
            # pow2 buckets + this capacity), exactly like the resident
            # bank's bucket warmup
            bank = churn_cycle(sharded, "warm")
            sizes0 = {
                name: fn._cache_size()
                for name, fn in [
                    ("write", sh_mod._sh_write_slot),
                    ("read", sh_mod._sh_read_slot),
                    ("serve", sh_mod._sh_mean_var),
                    ("update", sh_mod._sh_update_scatter),
                ]
            }
            # pin: an identical-shape churn cycle must compile NOTHING
            bank = churn_cycle(bank, "pin")
            for name, fn in [
                ("write", sh_mod._sh_write_slot),
                ("read", sh_mod._sh_read_slot),
                ("serve", sh_mod._sh_mean_var),
                ("update", sh_mod._sh_update_scatter),
            ]:
                assert fn._cache_size() == sizes0[name], (
                    name, fn._cache_size(), sizes0[name]
                )
        """)

    def test_placement_determinism(self):
        run_sub("""
            # round-robin FIT placement: tenant i -> shard i mod S, packed
            # from each shard's lowest local slot (from_bank instead
            # preserves the resident slot layout)
            fitted = ShardedGPBank.fit(Xb, yb, spec, mesh)
            C_l = fitted.shard_capacity
            for i in range(B):
                assert fitted.shard_of(i) == i % S
                assert fitted.slot_of(i) == (i % S) * C_l + i // S

            # least-loaded insert, ties broken by lowest shard id
            st = fitted.state(0)
            b = fitted.evict(1).evict(5)         # shard 1 now lightest
            b = b.insert("a", st)
            assert b.shard_of("a") == 1
            b = b.insert("b", st)                # shard 1 still one short
            assert b.shard_of("b") == 1

            # deterministic rebalance: fullest shard donates its highest
            # occupied local slot until spread <= 1; identical runs give
            # identical assignments
            def scenario():
                bb = fitted
                for t in [0, 4, 8, 12]:          # empty shard 0
                    bb = bb.evict(t)
                bb, moves = bb.rebalance()
                return moves, {t: bb.shard_of(t) for t in bb.tenants}
            m1, a1 = scenario()
            m2, a2 = scenario()
            assert m1 == m2 and a1 == a2
            assert m1 > 0
        """)


class TestShardedIntegration:
    def test_serve_fleet_sharded(self):
        run_sub("""
            # the operator's loop with --shards: the fleet spreads evenly
            # over the mesh, every query is served, and the trace carries
            # the per-shard dispatch and ingest events
            from repro.launch.serve_gp import serve_fleet
            from repro.obs import Tracer

            tracer = Tracer()
            out = serve_fleet(tenants=16, n_train=16, n=4, rounds=2,
                              queries_per_round=32,
                              observations_per_round=16, microbatch=8,
                              shards=S, tracer=tracer)
            assert out["shards"] == S
            assert out["shard_occupancy"] == [B // S] * S
            for h in out["rounds"]:
                assert h["timeouts"] == 0 and np.isfinite(h["rmse"])
            assert out["latency"]["overall"]["completed"] == 64
            names = {ev["name"] for ev in tracer.events()}
            assert {"shard_dispatch", "shard_ingest"} <= names
        """)

    def test_router_engine_tiered(self):
        run_sub("""
            import tempfile
            from repro.bank import BankRouter, FleetEngine, TieredBank
            from repro.obs import MetricsRegistry, Tracer

            reg = MetricsRegistry()
            tracer = Tracer()
            router = BankRouter(sharded, microbatch=8,
                                metrics=reg, tracer=tracer)
            eng = FleetEngine(router, metrics=reg, tracer=tracer)

            # engine drain parity vs direct resident serving
            tickets = [eng.submit(t, np.asarray(Xq[i]))
                       for i, t in enumerate(tenants)]
            results = eng.drain()
            mu_r, _ = resident.mean_var(tenants, Xq)
            mu_e = np.array([results[tk].mu for tk in tickets])
            np.testing.assert_allclose(mu_e, np.asarray(mu_r), atol=1e-5)

            # sharded ingest parity: observe + ingest, compare against the
            # resident bank updated with the same rows
            obs_t = [2, 9]
            xr = rng.uniform(-1, 1, (len(obs_t), P)).astype(np.float32)
            yr = rng.normal(size=len(obs_t)).astype(np.float32)
            for i, t in enumerate(obs_t):
                eng.observe(t, xr[i], yr[i])
            eng.ingest()
            res2 = resident.update(
                obs_t, jnp.asarray(xr[:, None, :]), jnp.asarray(yr[:, None])
            )
            mu_r2, _ = res2.mean_var(tenants, Xq)
            mu_s2, _ = router.bank.mean_var(tenants, Xq)
            # resident vs shard-local rank-1 lowering: per-tenant
            # conditioning (n_rows=8 << M=64) amplifies the f32 path
            # difference on the worst element; the dedicated parity test
            # pins the like-for-like update at 1e-5
            np.testing.assert_allclose(np.asarray(mu_s2),
                                       np.asarray(mu_r2), rtol=0, atol=1e-4)

            # per-shard telemetry: occupancy/backlog gauges + shard ids on
            # the dispatch/ingest trace events
            snap = reg.snapshot()
            gnames = {k.split("{")[0] for k in snap["gauges"]}
            assert "bank_shard_occupancy" in gnames
            names = {ev.get("name") for ev in tracer.events()}
            assert "shard_dispatch" in names and "shard_ingest" in names

            # router rebalance swaps the bank and counts moves
            for t in [t for t in router.bank.tenants
                      if router.bank.shard_of(t) == 0]:
                router.bank = router.bank.evict(t)
            router.rebalance(threshold=1)
            occ = router.bank.shard_occupancy()
            assert occ.max() - occ.min() <= 1
            snap = reg.snapshot()
            moves = [v for k, v in snap["counters"].items()
                     if k.startswith("bank_rebalance_total")]
            assert sum(moves) > 0

            # tiered paging: page-out then page-in lands the tenant on the
            # least-loaded shard through the recompile-free insert
            with tempfile.TemporaryDirectory() as cold:
                tb = TieredBank(router.bank, cold)
                t0 = tb.hot_tenants[0]
                tb.evict_to_cold(t0)
                assert t0 not in tb.bank.tenants
                least = int(np.argmin(tb.bank.shard_occupancy()))
                tb.page_in(t0)
                assert tb.bank.shard_of(t0) == least
        """)
