"""Sharded mega-bank scaling: the tenant axis across a device mesh.

The sharding claim behind ``repro.bank.ShardedGPBank``: at fleet sizes a
single device cannot hold or serve fast enough, splitting the stacked
``FAGPState``'s leading tenant axis across an S-way 'bank' mesh divides
every serving and fit executable's work by S with ZERO cross-shard
collectives on the hot path — each device runs the identical shard-local
program on its B/S-tenant slice.  Parity is absolute: the sharded bank,
the resident bank, and a Python loop of single-model calls all serve the
same answers (asserted here ≤1e-5 abs, gated by ``tools/check_bench.py``).

This container is a single-core CPU host, so S host devices time-slice
one core and the fused sharded WALL time cannot beat the resident bank
(it is gated here as an overhead ratio instead: sharded wall / resident
wall ≤ 2.0 — sharding must not add dispatch bloat).  The SCALING claim is
measured as the per-device critical path: the wall time of the same
executable over a B/S-tenant slice — exactly what each device computes
concurrently on real parallel hardware — giving a projected speedup
``T_resident(B) / T_slice(B/S)`` (gated ≥2.5 at S=8 for both serving and
fit).  ``host_cores`` and the method note are recorded in the payload so
a reader can tell projected from measured numbers.

Also driven here: an engine-traced segment (``FleetEngine`` over the
sharded bank) recording sustained QPS and emitting the per-shard
``shard_dispatch`` / ``shard_ingest`` / ``rebalance`` trace events that
``tools/check_trace.py --expect`` pins in CI.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.shard_scaling \\
      [--smoke | --full] [--trace-out FILE]

Under ``JAX_PLATFORMS=cpu`` the 8-device host flag
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) is set when
absent — it must reach the process before jax initializes its platform,
which is why this module touches ``os.environ`` before any jax import.  On
an accelerator the flag is left alone and the shard sweep stops at the
visible device count.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

# must precede ANY jax import: the host platform device count is fixed at
# first jax initialization
_FLAG = "--xla_force_host_platform_device_count"
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and _FLAG not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=8"
    ).strip()

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from repro.bank import (                                # noqa: E402
    BankRouter, FleetEngine, GPBank, ShardedGPBank,
)
from repro.core.gp import GP                            # noqa: E402
from repro.data import make_gp_dataset                  # noqa: E402
from repro.launch.mesh import make_bank_mesh            # noqa: E402
from repro.obs import MetricsRegistry, Tracer           # noqa: E402

from .common import bench_spec, emit, time_fn           # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
JSON_PATH = ROOT / "BENCH_shard.json"

# the acceptance shape: B=1024 small tenants (n=8, p=2 -> M=64) across a
# shard-count sweep; smoke keeps B (the ≥2.5x projected-speedup gate is a
# claim about THIS fleet size) and trims queries/engine traffic
B_MAIN, N_ROWS, P, N_MERCER = 1024, 8, 2, 8
SHARD_SWEEP = (1, 2, 4, 8)
PARITY_TENANTS = 64     # loop-of-singles parity subset (loop cost is O(B))


def _fleet_problem(B, nq, *, seed=0, backend="jnp"):
    rng = np.random.default_rng(seed)
    spec = bench_spec("hermite", P, n=N_MERCER,
                      num_features=(N_MERCER ** P) // 2, backend=backend)
    Xb = np.zeros((B, N_ROWS, P), np.float32)
    yb = np.zeros((B, N_ROWS), np.float32)
    for s in range(B):
        X, y, *_ = make_gp_dataset(N_ROWS, P, seed=seed + s)
        Xb[s], yb[s] = np.asarray(X), np.asarray(y)
    Xq = rng.uniform(-1, 1, size=(nq, P)).astype(np.float32)
    tenants = rng.integers(0, B, nq)
    return spec, jnp.asarray(Xb), jnp.asarray(yb), Xq, tenants


def _loop_of_singles(bank, tenants, Xq_np, subset):
    """Per-tenant single-model calls over the parity subset (the baseline
    a sharded bank replaces, served from the bank's own states)."""
    out_mu = np.full(len(tenants), np.nan, np.float32)
    out_var = np.full(len(tenants), np.nan, np.float32)
    for t in subset:
        rows = np.flatnonzero(tenants == t)
        if rows.size == 0:
            continue
        gp = GP.from_state(bank.state(int(t)))
        mu, var = gp.mean_var(jnp.asarray(Xq_np[rows]))
        out_mu[rows] = np.asarray(mu)
        out_var[rows] = np.asarray(var)
    return out_mu, out_var


def _max_abs(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def _engine_segment(sharded, *, nq, microbatch, tracer, metrics, seed=0):
    """Mixed-tenant traffic through the pipelined engine over the sharded
    bank: sustained QPS, plus the per-shard trace events CI pins."""
    import time as _time

    rng = np.random.default_rng(seed)
    B = len(sharded)
    router = BankRouter(sharded, microbatch=microbatch,
                        metrics=metrics, tracer=tracer)
    eng = FleetEngine(router, metrics=metrics, tracer=tracer)
    q_tenants = rng.integers(0, B, nq)
    Xq = rng.uniform(-1, 1, size=(nq, P)).astype(np.float32)
    # warm the dispatch path (compile outside the timed region)
    for i in range(microbatch):
        eng.submit(int(q_tenants[i]), Xq[i])
    eng.drain()
    t0 = _time.perf_counter()
    for i in range(nq):
        eng.submit(int(q_tenants[i]), Xq[i])
    eng.drain()
    qps = nq / (_time.perf_counter() - t0)
    # a short observation burst exercises the sharded ingest scatter
    for i in range(microbatch):
        t = int(q_tenants[i])
        eng.observe(t, Xq[i], np.float32(0.0))
    eng.ingest()
    # unbalance one shard, then rebalance (emits the 'rebalance' span and
    # bumps bank_rebalance_total)
    victims = [t for t in list(router.bank.tenants)
               if router.bank.shard_of(t) == 0][:2]
    for t in victims:
        router.bank = router.bank.evict(t)
    router.rebalance(threshold=1)
    return qps


def run(full: bool = False, smoke: bool = False, trace_out=None):
    results = []

    def record(name, seconds, derived=""):
        results.append(
            {"name": name, "seconds": seconds, "derived": derived}
        )

    B = B_MAIN
    nq = 512 if smoke else 2048
    spec, Xb, yb, Xq_np, tenants = _fleet_problem(B, nq)
    Xq = jnp.asarray(Xq_np)

    # -- resident baseline ---------------------------------------------------
    resident = GPBank.fit(Xb, yb, spec)
    tenant_list = [int(t) for t in tenants]
    t_fit_res = time_fn(lambda: GPBank.fit(Xb, yb, spec).stack.u)
    t_serve_res = time_fn(lambda: resident.mean_var(tenant_list, Xq))
    record("resident-fit", t_fit_res, f"B={B}")
    record("resident-mean_var", t_serve_res, f"B={B};nq={nq}")
    emit("shard/resident-fit", t_fit_res, f"B={B}")
    emit("shard/resident-mean_var", t_serve_res, f"B={B};nq={nq}")
    mu_res, var_res = resident.mean_var(tenant_list, Xq)

    parity = {}
    projected = {}
    overhead = {}
    sweep = tuple(S for S in (SHARD_SWEEP if not smoke else (1, 8))
                  if S <= len(jax.devices()))
    for S in sweep:
        mesh = make_bank_mesh(S)
        sharded = ShardedGPBank.from_bank(resident, mesh)
        # fused wall: all S shard programs time-slice this host's core(s);
        # gated as an overhead ratio, not a speedup
        t_fit_sh = time_fn(
            lambda: ShardedGPBank.fit(Xb, yb, spec, mesh).stack.u
        )
        t_serve_sh = time_fn(lambda: sharded.mean_var(tenant_list, Xq))
        # per-device critical path: the SAME executables over the B/S
        # slice each device owns — what runs concurrently on real
        # parallel hardware
        Bs = B // S
        res_s = GPBank.fit(Xb[:Bs], yb[:Bs], spec)
        t_fit_slice = time_fn(
            lambda: GPBank.fit(Xb[:Bs], yb[:Bs], spec).stack.u
        )
        # each shard's dispatch sees ~nq/S of the mixed-tenant rows
        # (bucketed per shard): the slice serves that share from its
        # B/S-tenant bank
        nq_s = max(1, nq // S)
        slice_tenants = [t % Bs for t in tenant_list[:nq_s]]
        Xq_s = Xq[:nq_s]
        t_serve_slice = time_fn(
            lambda: res_s.mean_var(slice_tenants, Xq_s)
        )
        tag = f"B={B};S={S};nq={nq}"
        record(f"sharded-fit-S{S}", t_fit_sh, tag)
        record(f"sharded-mean_var-S{S}", t_serve_sh, tag)
        record(f"slice-fit-S{S}", t_fit_slice, f"B={Bs};S={S}")
        record(f"slice-mean_var-S{S}", t_serve_slice,
               f"B={Bs};S={S};nq={nq}")
        projected[f"fit_S{S}"] = t_fit_res / t_fit_slice
        projected[f"serve_S{S}"] = t_serve_res / t_serve_slice
        overhead[f"fit_S{S}"] = t_fit_sh / t_fit_res
        overhead[f"serve_S{S}"] = t_serve_sh / t_serve_res
        emit(f"shard/sharded-mean_var-S{S}", t_serve_sh,
             f"{tag};projected={projected[f'serve_S{S}']:.1f}x")

        if S == max(sweep):
            # -- parity: sharded vs resident (all queries) and vs a loop
            #    of single-model calls (subset of tenants, full coverage)
            mu_sh, var_sh = sharded.mean_var(tenant_list, Xq)
            parity["sharded_vs_resident"] = {
                "mean_abs": _max_abs(mu_sh, mu_res),
                "var_abs": _max_abs(var_sh, var_res),
            }
            subset = np.arange(PARITY_TENANTS)
            mu_l, var_l = _loop_of_singles(sharded, tenants, Xq_np, subset)
            rows = np.flatnonzero(np.isin(tenants, subset))
            parity["sharded_vs_loop"] = {
                "mean_abs": _max_abs(np.asarray(mu_sh)[rows], mu_l[rows]),
                "var_abs": _max_abs(np.asarray(var_sh)[rows], var_l[rows]),
            }
            for k, rec in parity.items():
                assert rec["mean_abs"] <= 1e-5 and rec["var_abs"] <= 1e-5, \
                    (k, rec)
            # the sharded FIT is a different lowering of the same moments
            # (per-shard accumulation order, data-axis psum tree), so its
            # agreement with the resident fit is f32-summation-order
            # limited — tracked under its own key with a 5e-5 gate, apart
            # from the exact serving parities above
            fitted_sh = ShardedGPBank.fit(Xb, yb, spec, mesh)
            mu_f, var_f = fitted_sh.mean_var(tenant_list, Xq)
            fit_agreement = {
                "mean_abs": _max_abs(mu_f, mu_res),
                "var_abs": _max_abs(var_f, var_res),
            }
            assert fit_agreement["mean_abs"] <= 5e-5, fit_agreement
            assert fit_agreement["var_abs"] <= 5e-5, fit_agreement

            # -- engine-driven traced segment over the largest mesh
            reg = MetricsRegistry()
            tracer = Tracer()
            qps = _engine_segment(
                sharded, nq=min(nq, 512), microbatch=64,
                tracer=tracer, metrics=reg, seed=1,
            )
            record(f"engine-sustained-S{S}", 1.0 / qps,
                   f"B={B};S={S};qps={qps:.0f}")
            if trace_out:
                n = tracer.write_jsonl(trace_out)
                emit("shard/trace-written", 0.0, f"{n} events")

    payload = {
        "schema": 1,
        "smoke": bool(smoke),
        "config": {
            "B": B, "n_rows": N_ROWS, "p": P, "n": N_MERCER, "nq": nq,
            "shard_sweep": list(sweep),
            "host_cores": os.cpu_count(),
            "devices": jax.device_count(),
        },
        "method": (
            "single-core host: 'projected_speedup' is the per-device "
            "critical path T_resident(B)/T_slice(B/S) — the wall time of "
            "the same executable over the B/S-tenant, nq/S-query slice "
            "each device runs concurrently on parallel hardware; "
            "'wall_overhead' is the fused sharded wall / resident wall on "
            "THIS host (S devices time-slicing one core) — gated ≤2.0 at "
            "S=1 (pure shard_map overhead) and ≤4.0 at S=8 (per-shard "
            "pow2 buckets pad the mixed-tenant load up to 2x)"
        ),
        "results": results,
        "parity_abs": parity,
        "fit_agreement_abs": fit_agreement,
        "projected_speedup": projected,
        "wall_overhead": overhead,
    }
    JSON_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    emit("shard/json-written", 0.0, str(JSON_PATH.name))
    return payload


def main():
    argv = sys.argv[1:]
    trace_out = None
    if "--trace-out" in argv:
        trace_out = argv[argv.index("--trace-out") + 1]
    run(full="--full" in argv, smoke="--smoke" in argv,
        trace_out=trace_out)


if __name__ == "__main__":
    main()
