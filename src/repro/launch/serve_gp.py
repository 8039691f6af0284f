"""GP serving loops: one session, or a whole fleet through the bank router.

Two production shapes of the paper's workload:

* ``serve_gp``    — ONE fitted session serves microbatched ``mean_var``
  queries while new observations stream in (``GP.update`` rank-k ingest).
* ``serve_fleet`` — MANY small independent sessions (one per tenant)
  served concurrently: the sessions live device-resident in a
  :class:`~repro.bank.GPBank` (one stacked state, one executable for the
  whole fleet) and traffic flows through a :class:`~repro.bank.BankRouter`
  that coalesces per-tenant query/observation queues into padded
  mixed-tenant microbatches.  By default the router is driven by the
  pipelined :class:`~repro.bank.FleetEngine` (``engine="pipelined"``):
  dispatch-ahead blocks with no per-tick ``block_until_ready``, per-tenant
  deadlines answered with the documented timeout sentinel, queue-budget
  backpressure, arrival-rate-autotuned microbatch buckets, and per-tenant
  p50/p99 + sustained-QPS metrics in the returned history.
  ``engine="sync"`` keeps the strict coalesce -> dispatch -> block ->
  respond loop (the baseline ``benchmarks/serve_latency.py`` beats).

Both loops speak self-describing sessions: the spec (index set, backend,
block size) is baked in at fit time, so neither the query path nor the
ingest path re-passes configuration.

  PYTHONPATH=src python -m repro.launch.serve_gp --backend pallas \\
      --n-train 2048 --p 2 --n 8 --rounds 4 --update-size 64 \\
      --queries 512 --microbatch 128
  PYTHONPATH=src python -m repro.launch.serve_gp --fleet 64 --n-train 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bank import (
    BankRouter, FleetEngine, GPBank, ShardedGPBank, TieredBank,
)
from repro.core import fagp
from repro.core.gp import GP, GPSpec
from repro.data import make_gp_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import (
    NULL,
    NULL_TRACER,
    MetricsRegistry,
    Tracer,
    serving_watchdog,
    start_metrics_server,
)
from repro.obs import metrics as obs_metrics

__all__ = ["serve_gp", "serve_fleet", "microbatched_mean_var"]


def microbatched_mean_var(gp, Xs, *, microbatch: int):
    """``mean_var`` in fixed-size microbatches (padded tail).

    ``gp`` is a :class:`GP` session (a spec-carrying :class:`FAGPState` is
    also accepted and wrapped).  Returns (mu, var, per_batch_seconds).
    Every call sees the same (B, p) shape, so the serving path compiles
    exactly once per state shape.  Padding and microbatch slicing happen
    once, up front, outside the timed region — ``per_batch_seconds``
    measures only ``mean_var``.
    """
    if isinstance(gp, fagp.FAGPState):
        gp = GP.from_state(gp)
    Nq = Xs.shape[0]
    nb = max(1, (Nq + microbatch - 1) // microbatch)
    pad = nb * microbatch - Nq
    Xp = jnp.pad(Xs, ((0, pad), (0, 0)))
    blocks = [
        jax.lax.dynamic_slice_in_dim(Xp, i * microbatch, microbatch)
        for i in range(nb)
    ]
    jax.block_until_ready(blocks)
    mus, variances, times = [], [], []
    for blk in blocks:
        t0 = time.perf_counter()
        mu, var = gp.mean_var(blk)
        jax.block_until_ready((mu, var))
        times.append(time.perf_counter() - t0)
        mus.append(np.asarray(mu))
        variances.append(np.asarray(var))
    mu = np.concatenate(mus)[:Nq]
    var = np.concatenate(variances)[:Nq]
    return mu, var, times


def serve_gp(
    *,
    backend: str = "jnp",
    n_train: int = 2048,
    p: int = 2,
    n: int = 8,
    rounds: int = 4,
    update_size: int = 64,
    queries: int = 512,
    microbatch: int = 128,
    noise: float = 0.05,
    seed: int = 0,
) -> dict:
    spec = GPSpec.create(
        n, eps=jnp.full((p,), 0.8), rho=2.0, noise=noise, backend=backend,
    )
    # n_train initial rows + rounds * update_size streamed rows, one pool
    total = n_train + rounds * update_size
    X_all, y_all, Xs, ys = make_gp_dataset(total, p, noise=noise, seed=seed)
    X0, y0 = X_all[:n_train], y_all[:n_train]

    t0 = time.perf_counter()
    gp = GP.fit(X0, y0, spec)
    jax.block_until_ready(gp.state.u)
    t_fit = time.perf_counter() - t0

    Xq = Xs[:queries] if queries <= Xs.shape[0] else Xs
    ysq = np.asarray(ys)[: Xq.shape[0]]

    history = []
    for r in range(rounds):
        lo = n_train + r * update_size
        Xn, yn = X_all[lo : lo + update_size], y_all[lo : lo + update_size]
        t0 = time.perf_counter()
        gp = gp.update(Xn, yn)
        jax.block_until_ready(gp.state.u)
        t_update = time.perf_counter() - t0

        mu, var, times = microbatched_mean_var(gp, Xq, microbatch=microbatch)
        rmse = float(np.sqrt(np.mean((mu - ysq) ** 2)))
        times.sort()
        history.append({
            "round": r,
            "rows_absorbed": int(lo + update_size),
            "update_s": t_update,
            "predict_p50_s": times[len(times) // 2],
            "queries_per_s": Xq.shape[0] / sum(times),
            "rmse": rmse,
        })
    return {"fit_s": t_fit, "rounds": history, "M": gp.n_features}


def serve_fleet(
    *,
    backend: str = "jnp",
    tenants: int = 64,
    n_train: int = 64,
    p: int = 2,
    n: int = 8,
    rounds: int = 4,
    queries_per_round: int = 512,
    observations_per_round: int = 128,
    microbatch: int = 64,
    ingest_chunk: int = 16,
    noise: float = 0.05,
    seed: int = 0,
    reopt_every: int = 0,
    reopt_min_rows: int = 16,
    reopt_steps: int = 25,
    reopt_restarts: int = 2,
    engine: str = "pipelined",
    max_in_flight: int = 4,
    queue_budget: int = 4096,
    slo_s: float | None = None,
    capacity: int | None = None,
    cold_dir: str | None = None,
    window: int = 0,
    shards: int = 0,
    metrics=None,
    tracer=None,
    watchdog=None,
) -> dict:
    """Serve a fleet of ``tenants`` small independent GPs concurrently.

    Each tenant observes its own shifted copy of the synthetic target.
    Every round, mixed-tenant query traffic (uniformly random tenant per
    query) flows through the serving frontend in padded microbatches, and
    per-tenant observation streams are absorbed with batched
    ``GPBank.update`` rounds.  Reported per round: ingest time, query
    wall time, fleet-wide queries/s, timeout count, and RMSE against each
    tenant's own target; the returned dict additionally carries the
    engine's cumulative latency metrics (per-tenant p50/p99, sustained
    QPS, bucket usage) when ``engine="pipelined"``.

    ``engine`` selects the serving frontend: ``"pipelined"`` (default)
    drives a :class:`~repro.bank.FleetEngine` — queries dispatch ahead
    while the host packs the next block, expired tickets (``slo_s``) get
    the timeout sentinel instead of a seat in a padded block, and the
    block size autotunes to the arrival rate; ``"sync"`` is the strict
    submit-all / flush / block loop.

    ``reopt_every > 0`` additionally re-optimizes STALE tenants every that
    many rounds: tenants that absorbed >= ``reopt_min_rows`` observations
    since their last optimization are re-fit with one batched
    ``GPBank.optimize`` run over their accumulated data
    (``router.reoptimize``) — the bank becomes heterogeneous and each
    tenant serves under its own learned hyperparameters.

    ``cold_dir`` turns the fleet ELASTIC (pipelined engine only): the
    bank becomes a :class:`~repro.bank.TieredBank` with ``capacity`` hot
    slots (default: all tenants resident) fronting versioned per-tenant
    checkpoints under ``cold_dir`` — traffic to cold tenants warm-restores
    them through the engine, evicting LRU tenants back to disk, with zero
    new executables across the churn.  ``window > 0`` additionally ages
    drifted tenants before re-optimization: everything older than each
    stale tenant's newest ``window`` rows is forgotten via the batched
    rank-k Cholesky downdate (masked-refit fallback on lost positive
    definiteness), so re-learned hyperparameters track the CURRENT regime
    instead of averaging over the tenant's whole history.

    ``shards > 0`` shards the fleet's tenant axis across a ``shards``-way
    'bank' device mesh (:class:`~repro.bank.ShardedGPBank`): every serving
    and ingest executable runs shard-local with no cross-shard collectives,
    the router tracks per-shard occupancy/backlog, and paged-in tenants
    land on the least-loaded shard.  Needs ``shards`` visible devices (on
    CPU export ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before jax starts) and is homogeneous-only — incompatible with
    ``reopt_every`` (per-tenant learned hyperparameters).

    ``metrics`` / ``tracer`` / ``watchdog`` (``repro.obs``) thread fleet
    telemetry through every stage: the router, the pipelined engine, the
    tiered lifecycle, and stale-tenant re-optimization all emit into the
    same registry and trace buffer.  All three default to the shared null
    objects (zero overhead); pass real instances (or use the
    ``--metrics-port`` / ``--trace-out`` CLI flags) to turn them on.
    """
    rng = np.random.default_rng(seed)
    spec = GPSpec.create(
        n, eps=jnp.full((p,), 0.8), rho=2.0, noise=noise, backend=backend,
    )
    # per-tenant pools: tenant t sees the target shifted by its own offset
    offsets = rng.uniform(-1.0, 1.0, size=tenants).astype(np.float32)
    total = n_train + rounds * max(
        1, observations_per_round // max(1, tenants)
    ) + observations_per_round
    Xb = np.zeros((tenants, n_train, p), np.float32)
    yb = np.zeros((tenants, n_train), np.float32)
    pools = []
    for t in range(tenants):
        X_all, y_all, _, _ = make_gp_dataset(
            total, p, noise=noise, seed=seed + t
        )
        y_all = jnp.asarray(np.asarray(y_all) + offsets[t])
        Xb[t] = np.asarray(X_all[:n_train])
        yb[t] = np.asarray(y_all[:n_train])
        pools.append((np.asarray(X_all), np.asarray(y_all)))

    if engine not in ("pipelined", "sync"):
        raise ValueError(
            f"engine must be 'pipelined' or 'sync', got {engine!r}"
        )
    if cold_dir is not None and engine != "pipelined":
        raise ValueError(
            "a tiered fleet (cold_dir) needs the pipelined engine: the "
            "sync router fail-fasts on cold tenants instead of paging"
        )
    if (capacity is not None or window) and cold_dir is None:
        raise ValueError(
            "capacity/window need a cold tier; pass cold_dir"
        )
    if shards and reopt_every:
        raise ValueError(
            "a sharded fleet is homogeneous-only (one spec across all "
            "shards); per-tenant re-optimization (reopt_every) needs the "
            "resident bank"
        )
    metrics = NULL if metrics is None else metrics
    tracer = NULL_TRACER if tracer is None else tracer
    t0 = time.perf_counter()
    tiered = None
    if cold_dir is not None:
        tiered = TieredBank.fit(
            jnp.asarray(Xb), jnp.asarray(yb), spec, cold_dir=cold_dir,
            capacity=capacity, window=window,
            metrics=metrics, tracer=tracer,
        )
        bank = tiered.bank
    else:
        bank = GPBank.fit(jnp.asarray(Xb), jnp.asarray(yb), spec)
    if shards:
        from repro.launch.mesh import make_bank_mesh
        bank = ShardedGPBank.from_bank(
            bank, make_bank_mesh(shards), pad_capacity=True
        )
        if tiered is not None:
            tiered.adopt(bank)
    jax.block_until_ready(bank.stack.u)
    t_fit = time.perf_counter() - t0

    router = BankRouter(bank, microbatch=microbatch,
                        ingest_chunk=ingest_chunk,
                        metrics=metrics, tracer=tracer)
    eng = None
    if engine == "pipelined":
        eng = FleetEngine(
            router, max_in_flight=max_in_flight,
            queue_budget=queue_budget, default_slo_s=slo_s,
            tiered=tiered,
            metrics=metrics, tracer=tracer, watchdog=watchdog,
        )
    consumed = [n_train] * tenants
    history = []
    for r in range(rounds):
        # -- ingest: each tenant streams a few fresh observations ----------
        front = eng if eng is not None else router
        for _ in range(observations_per_round):
            t = int(rng.integers(0, tenants))
            X_all, y_all = pools[t]
            i = consumed[t] % X_all.shape[0]
            consumed[t] += 1
            front.observe(t, X_all[i], y_all[i])
        t0 = time.perf_counter()
        absorbed = front.ingest()
        jax.block_until_ready(router.bank.stack.u)
        t_ingest = time.perf_counter() - t0

        # -- periodic re-optimization of stale tenants ---------------------
        t_reopt, n_reopt, n_aged = 0.0, 0, 0
        if reopt_every and (r + 1) % reopt_every == 0:
            # cold tenants keep their drift counters (retain=) — paging a
            # tenant out for capacity must not reset its staleness
            stale = (router.stale_tenants(reopt_min_rows,
                                          retain=tiered.tenants)
                     if tiered is not None
                     else router.stale_tenants(reopt_min_rows))
            if stale and tiered is not None and window:
                # age BEFORE re-optimizing: forget rows outside each stale
                # tenant's sliding window (batched downdate + refit
                # fallback) so the re-learned hyperparameters fit the
                # current regime, then re-optimize on the retained window
                tiered.adopt(router.bank)
                aged = tiered.age(stale)
                router.bank = tiered.bank
                n_aged = aged["forgotten_rows"]
            if stale:
                # row axis padded to the FIXED pool size (masked): a
                # max-consumed row count would grow every reopt round and
                # retrace the lane executables each time.  (The tenant
                # axis still varies with the stale set — bounded by the
                # distinct stale-set sizes, not by round count.)
                n_max = window if (tiered is not None and window) else total
                Xo = np.zeros((len(stale), n_max, p), np.float32)
                yo = np.zeros((len(stale), n_max), np.float32)
                mo = np.zeros((len(stale), n_max), np.float32)
                for i, t in enumerate(stale):
                    if tiered is not None and window:
                        # aged fleet: learn from the RETAINED window only
                        # (the forgotten rows are gone from the
                        # factorization — the hypers should follow)
                        for j, (xr, yr) in enumerate(tiered._rows[t]):
                            Xo[i, j], yo[i, j], mo[i, j] = xr, yr, 1.0
                        continue
                    X_all, y_all = pools[t]
                    rows = min(consumed[t], X_all.shape[0])
                    Xo[i, :rows] = X_all[:rows]
                    yo[i, :rows] = y_all[:rows]
                    mo[i, :rows] = 1.0
                t0 = time.perf_counter()
                router.reoptimize(
                    stale, jnp.asarray(Xo), jnp.asarray(yo),
                    mask=jnp.asarray(mo), restarts=reopt_restarts,
                    steps=reopt_steps, seed=seed,
                )
                jax.block_until_ready(router.bank.stack.u)
                t_reopt = time.perf_counter() - t0
                n_reopt = len(stale)
                if tiered is not None:
                    tiered.adopt(router.bank)

        # -- queries: mixed-tenant traffic through the frontend ------------
        q_tenants = rng.integers(0, tenants, queries_per_round)
        Xq = rng.uniform(-1.0, 1.0, size=(queries_per_round, p)).astype(
            np.float32
        )
        timeouts = 0
        if eng is not None:
            # pipelined: submission itself dispatches blocks ahead
            # (auto_pump), drain() overlaps packing with device execution
            t0 = time.perf_counter()
            tickets = [
                eng.submit(int(t), Xq[i]) for i, t in enumerate(q_tenants)
            ]
            results = eng.drain()
            t_query = time.perf_counter() - t0
            served = {
                tk: i for i, tk in enumerate(tickets)
                if not results[tk].timed_out
            }
            timeouts = len(tickets) - len(served)
            mu = np.array([results[tk].mu for tk in served])
            truth = (np.sum(np.cos(Xq), axis=1)
                     + offsets[q_tenants])[list(served.values())]
        else:
            tickets = [
                router.submit(int(t), Xq[i])
                for i, t in enumerate(q_tenants)
            ]
            t0 = time.perf_counter()
            results = router.flush()
            t_query = time.perf_counter() - t0
            mu = np.array([results[tk][0] for tk in tickets])
            # RMSE of each query against its own tenant's (noise-free)
            # Eq. 21 target sum_j cos(x_j) + offset_t
            truth = np.sum(np.cos(Xq), axis=1) + offsets[q_tenants]
        rmse = float(np.sqrt(np.mean((mu - truth) ** 2)))
        nb = max(1, (queries_per_round + microbatch - 1) // microbatch)
        history.append({
            "round": r,
            "rows_absorbed": absorbed,
            "ingest_s": t_ingest,
            "query_s": t_query,
            # one aggregate flush/drain is timed, so this is a
            # per-microbatch MEAN (serve_gp's predict_p50_s is a true
            # per-block median)
            "query_mean_s": t_query / nb,
            "queries_per_s": queries_per_round / t_query,
            "rmse": rmse,
            "timeouts": timeouts,
            "reopt_s": t_reopt,
            "reopt_tenants": n_reopt,
            "aged_rows": n_aged,
        })
    out = {
        "fit_s": t_fit,
        "tenants": tenants,
        "rounds": history,
        "M": bank.n_features,
        "engine": engine,
    }
    if shards:
        out["shards"] = shards
        out["shard_occupancy"] = [
            int(c) for c in router.bank.shard_occupancy()
        ]
    if eng is not None:
        out["latency"] = eng.metrics()
    elif metrics is not NULL:
        out["telemetry"] = metrics.snapshot()
    if tiered is not None:
        out["lifecycle"] = dict(
            tiered.stats, capacity=tiered.capacity,
            hot=len(tiered.hot_tenants), cold=len(tiered.cold_tenants),
        )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="jnp",
                    choices=fagp.available_backends())
    ap.add_argument("--fleet", type=int, default=0, metavar="B",
                    help="serve a bank of B tenants instead of one session")
    ap.add_argument("--n-train", type=int, default=2048)
    ap.add_argument("--p", type=int, default=2)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--update-size", type=int, default=64)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--microbatch", type=int, default=128)
    ap.add_argument("--reopt-every", type=int, default=0, metavar="K",
                    help="re-optimize stale tenants every K serving rounds")
    ap.add_argument("--engine", default="pipelined",
                    choices=["pipelined", "sync"],
                    help="fleet serving frontend (pipelined FleetEngine "
                         "vs the strict synchronous loop)")
    ap.add_argument("--max-in-flight", type=int, default=4,
                    help="dispatch-ahead depth of the pipelined engine")
    ap.add_argument("--slo", type=float, default=None, metavar="SECONDS",
                    help="per-ticket deadline; expired tickets get the "
                         "timeout sentinel instead of a device slot")
    ap.add_argument("--capacity", type=int, default=None, metavar="C",
                    help="hot slots in a tiered fleet (< --fleet pages "
                         "the rest to the cold tier); needs --cold-dir")
    ap.add_argument("--cold-dir", default=None, metavar="DIR",
                    help="cold-tier checkpoint directory (enables the "
                         "TieredBank lifecycle; pipelined engine only)")
    ap.add_argument("--shards", type=int, default=0, metavar="S",
                    help="shard the fleet's tenant axis across an S-way "
                         "'bank' device mesh (needs S visible devices; on "
                         "CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=S before launch)")
    ap.add_argument("--window", type=int, default=0, metavar="W",
                    help="sliding-window length: before each reopt, "
                         "forget rows older than each stale tenant's "
                         "newest W (rank-k downdate); needs --cold-dir")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus text at http://127.0.0.1:PORT"
                         "/metrics while the fleet runs (0 = ephemeral "
                         "port; fleet mode only)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write pipeline spans as Chrome-trace JSONL to "
                         "FILE on exit (load in chrome://tracing or "
                         "ui.perfetto.dev; fleet mode only)")
    ap.add_argument("--watchdog", default=None,
                    choices=["warn", "raise", "count"],
                    help="arm the recompile watchdog over the serving "
                         "executables (fleet mode only)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.fleet:
        obs_on = (args.metrics_port is not None or args.trace_out
                  or args.watchdog)
        reg = MetricsRegistry() if obs_on else None
        tracer = Tracer() if args.trace_out else None
        wd = (serving_watchdog(mode=args.watchdog, metrics=reg)
              if args.watchdog else None)
        server = None
        if reg is not None:
            # store.py counters (stale-tmp sweeps, async-checkpoint
            # failures) publish to the process default — point it here so
            # one scrape sees the whole fleet
            obs_metrics.set_default(reg)
        if args.metrics_port is not None:
            server = start_metrics_server(reg, port=args.metrics_port)
            print(f"metrics: {server.url}")
        try:
            r = serve_fleet(
                backend=args.backend, tenants=args.fleet,
                n_train=args.n_train, p=args.p, n=args.n,
                rounds=args.rounds,
                queries_per_round=args.queries,
                observations_per_round=args.update_size,
                microbatch=args.microbatch, reopt_every=args.reopt_every,
                engine=args.engine, max_in_flight=args.max_in_flight,
                slo_s=args.slo, capacity=args.capacity,
                cold_dir=args.cold_dir, window=args.window,
                shards=args.shards,
                metrics=reg, tracer=tracer, watchdog=wd,
            )
        finally:
            if tracer is not None and args.trace_out:
                n = tracer.write_jsonl(args.trace_out)
                print(f"trace: {n} events -> {args.trace_out}")
            if server is not None:
                server.shutdown()
            if reg is not None:
                obs_metrics.set_default(NULL)
        print(
            f"fleet of {r['tenants']} fitted in {r['fit_s']*1e3:.1f} ms "
            f"(M={r['M']} each; {r['engine']} engine)"
        )
        if "shards" in r:
            print(
                f"sharded across {r['shards']} devices; occupancy "
                f"{r['shard_occupancy']}"
            )
        for h in r["rounds"]:
            reopt = (
                f"; reopt {h['reopt_tenants']} tenants "
                f"{h['reopt_s']*1e3:.1f} ms" if h["reopt_tenants"] else ""
            )
            print(
                f"round {h['round']}: ingest {h['rows_absorbed']} rows "
                f"{h['ingest_s']*1e3:.1f} ms; query mean "
                f"{h['query_mean_s']*1e3:.2f} ms/microbatch; "
                f"{h['queries_per_s']:.0f} q/s; rmse {h['rmse']:.4f}"
                f"{'; ' + str(h['timeouts']) + ' timeouts' if h['timeouts'] else ''}"
                f"{reopt}"
            )
        if "latency" in r:
            o = r["latency"]["overall"]
            print(
                f"engine: p50 {o['p50_s']*1e3:.2f} ms, p99 "
                f"{o['p99_s']*1e3:.2f} ms per ticket; sustained "
                f"{o['sustained_qps']:.0f} q/s; {o['expired']} expired; "
                f"buckets {sorted(r['latency']['bucket_uses'].items())}"
            )
        if "lifecycle" in r:
            lc = r["lifecycle"]
            print(
                f"lifecycle: {lc['hot']}/{lc['capacity']} hot, "
                f"{lc['cold']} cold; {lc['warm_restores']} restores, "
                f"{lc['evictions']} evictions, {lc['cold_saves']} saves; "
                f"{lc['downdated_rows']} rows forgotten "
                f"({lc['refit_fallbacks']} refit fallbacks)"
            )
        return
    r = serve_gp(
        backend=args.backend, n_train=args.n_train, p=args.p, n=args.n,
        rounds=args.rounds, update_size=args.update_size,
        queries=args.queries, microbatch=args.microbatch,
    )
    print(f"initial fit {r['fit_s']*1e3:.1f} ms (M={r['M']})")
    for h in r["rounds"]:
        print(
            f"round {h['round']}: N={h['rows_absorbed']} "
            f"ingest {h['update_s']*1e3:.1f} ms; "
            f"predict p50 {h['predict_p50_s']*1e3:.2f} ms/microbatch; "
            f"{h['queries_per_s']:.0f} q/s; rmse {h['rmse']:.4f}"
        )


if __name__ == "__main__":
    main()
