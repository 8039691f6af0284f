"""Render EXPERIMENTS.md from experiments/dryrun/*.json + perf_log.md.

  PYTHONPATH=src python -m benchmarks.gen_experiments

Degrades gracefully when the experiments/ tree is absent (fresh checkout):
the section structure and regeneration instructions are emitted with empty
tables, so EXPERIMENTS.md always exists and always documents how to fill
itself in.
"""
from __future__ import annotations

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DRY = ROOT / "experiments" / "dryrun"
BASE = ROOT / "experiments" / "dryrun_baseline"

_NO_CELLS = (
    "*(no dry-run cells recorded in `experiments/dryrun/` — regenerate with"
    " the command in §Regenerating)*"
)


def load(d: Path):
    if not d.exists():
        return []
    return [json.loads(f.read_text()) for f in sorted(d.glob("*.json"))]


def expansion_rows() -> str:
    """Render BENCH_expansions.json (the kernel-family trajectory started by
    the --expansion benchmark axis) as a table, or a placeholder."""
    path = ROOT / "BENCH_expansions.json"
    if not path.exists():
        return ("*(no `BENCH_expansions.json` yet — run any benchmark with "
                "`--expansion`, e.g. the commands above)*")
    try:
        rows = json.loads(path.read_text()).get("results", [])
    except json.JSONDecodeError:
        rows = []
    if not rows:
        return "*(BENCH_expansions.json present but empty)*"
    out = ["| bench | expansion | name | µs/call | derived |",
           "|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['bench']} | {r['expansion']} | {r['name']} "
            f"| {r['seconds'] * 1e6:.1f} | {r['derived']} |"
        )
    return "\n".join(out)


def optimize_rows() -> str:
    """Render BENCH_optimize.json (the fleet-hyperopt trajectory) as a
    table, or a placeholder."""
    path = ROOT / "BENCH_optimize.json"
    if not path.exists():
        return ("*(no `BENCH_optimize.json` yet — run "
                "`PYTHONPATH=src python -m benchmarks.gp_hyperopt`)*")
    try:
        d = json.loads(path.read_text())
    except json.JSONDecodeError:
        return "*(BENCH_optimize.json unreadable)*"
    rows = d.get("results", [])
    if not rows:
        return "*(BENCH_optimize.json present but empty)*"
    out = ["| name | seconds | derived |", "|---|---|---|"]
    for r in rows:
        out.append(f"| {r['name']} | {r['seconds']:.3f} | {r['derived']} |")
    par = d.get("parity_abs", {})
    if par:
        worst = max(
            (v for rec in par.values() for v in rec.values()), default=0.0
        )
        out.append("")
        out.append(
            f"Worst bank-vs-loop parity across "
            f"{sorted(par)}: **{worst:g}** (gate: ≤1e-5, asserted "
            f"in-benchmark and by `tools/check_bench.py`)."
        )
    return "\n".join(out)


def serve_rows() -> str:
    """Render BENCH_serve.json (the pipelined-serving trajectory) as a
    table + the gated claims, or a placeholder."""
    path = ROOT / "BENCH_serve.json"
    if not path.exists():
        return ("*(no `BENCH_serve.json` yet — run "
                "`PYTHONPATH=src python -m benchmarks.serve_latency`)*")
    try:
        d = json.loads(path.read_text())
    except json.JSONDecodeError:
        return "*(BENCH_serve.json unreadable)*"
    rows = d.get("results", [])
    if not rows:
        return "*(BENCH_serve.json present but empty)*"
    out = ["| name | seconds | derived |", "|---|---|---|"]
    for r in rows:
        out.append(f"| {r['name']} | {r['seconds']:.4f} | {r['derived']} |")
    lat = d.get("latency", {})
    out.append("")
    out.append(
        f"Pipelined-vs-sync sustained speedup: "
        f"**{d.get('speedup_pipelined_vs_sync', float('nan')):.2f}×** "
        f"(gate: ≥1.5, hard-failed by `tools/check_bench.py`); overall "
        f"p50 {lat.get('p50_s', float('nan')) * 1e3:.2f} ms / p99 "
        f"{lat.get('p99_s', float('nan')) * 1e3:.2f} ms over "
        f"{len(lat.get('tenants', {}))} tenants; "
        f"{d.get('timeouts', 0)} deadline expiries (all under an "
        f"impossible SLO by construction), "
        f"{d.get('dropped_non_expired', 0)} non-expired tickets dropped "
        f"(gate: 0)."
    )
    return "\n".join(out)


def lifecycle_rows() -> str:
    """Render BENCH_lifecycle.json (the tiered-serving trajectory) as a
    table + the gated claims, or a placeholder."""
    path = ROOT / "BENCH_lifecycle.json"
    if not path.exists():
        return ("*(no `BENCH_lifecycle.json` yet — run "
                "`PYTHONPATH=src python -m benchmarks.tenant_churn`)*")
    try:
        d = json.loads(path.read_text())
    except json.JSONDecodeError:
        return "*(BENCH_lifecycle.json unreadable)*"
    rows = d.get("results", [])
    if not rows:
        return "*(BENCH_lifecycle.json present but empty)*"
    out = ["| name | seconds | derived |", "|---|---|---|"]
    for r in rows:
        out.append(f"| {r['name']} | {r['seconds']:.4f} | {r['derived']} |")
    par = d.get("parity_abs", {})
    if par:
        worst = max(
            (v for rec in par.values() for v in rec.values()), default=0.0
        )
        out.append("")
        out.append(
            f"Worst paged-vs-resident / downdate-vs-refit parity across "
            f"{sorted(par)}: **{worst:g}** (gate: ≤1e-5, asserted "
            f"in-benchmark and by `tools/check_bench.py`)."
        )
    life = d.get("lifecycle", {}).get("jnp", {})
    if life:
        out.append(
            f"Lifecycle churn during the run: {life.get('warm_restores', 0)}"
            f" warm restores, {life.get('evictions', 0)} evictions, "
            f"{life.get('cold_saves', 0)} cold saves — all through the "
            f"recompile-free insert/evict path."
        )
    return "\n".join(out)


def shard_rows() -> str:
    """Render BENCH_shard.json (the sharded mega-bank trajectory) as a
    table + the gated claims, or a placeholder."""
    path = ROOT / "BENCH_shard.json"
    if not path.exists():
        return ("*(no `BENCH_shard.json` yet — run "
                "`PYTHONPATH=src python -m benchmarks.shard_scaling`)*")
    try:
        d = json.loads(path.read_text())
    except json.JSONDecodeError:
        return "*(BENCH_shard.json unreadable)*"
    rows = d.get("results", [])
    if not rows:
        return "*(BENCH_shard.json present but empty)*"
    out = ["| name | seconds | derived |", "|---|---|---|"]
    for r in rows:
        out.append(f"| {r['name']} | {r['seconds']:.4f} | {r['derived']} |")
    proj = d.get("projected_speedup", {})
    over = d.get("wall_overhead", {})
    par = d.get("parity_abs", {})
    worst = max((v for rec in par.values() for v in rec.values()),
                default=float("nan"))
    cfg = d.get("config", {})
    out.append("")
    out.append(
        f"Projected per-device speedup at S=8 (critical path "
        f"T_resident/T_slice on a {cfg.get('host_cores', '?')}-core host "
        f"exposing {cfg.get('devices', '?')} devices): "
        f"**{proj.get('serve_S8', float('nan')):.1f}× serving / "
        f"{proj.get('fit_S8', float('nan')):.1f}× fit** (gate: ≥2.5, "
        f"hard-failed by `tools/check_bench.py`); fused sharded wall "
        f"overhead {over.get('serve_S8', float('nan')):.2f}× at S=8 "
        f"(gate: ≤4.0 — S host devices time-slice this machine's core). "
        f"Worst sharded-vs-resident / sharded-vs-loop serving parity: "
        f"**{worst:g}** (gate: ≤1e-5, asserted in-benchmark)."
    )
    return "\n".join(out)


def obs_rows() -> str:
    """Render BENCH_obs.json (the telemetry-overhead trajectory) as a
    table + the gated claims, or a placeholder."""
    path = ROOT / "BENCH_obs.json"
    if not path.exists():
        return ("*(no `BENCH_obs.json` yet — run "
                "`PYTHONPATH=src python -m benchmarks.serve_latency`)*")
    try:
        d = json.loads(path.read_text())
    except json.JSONDecodeError:
        return "*(BENCH_obs.json unreadable)*"
    rows = d.get("results", [])
    if not rows:
        return "*(BENCH_obs.json present but empty)*"
    out = ["| name | seconds | derived |", "|---|---|---|"]
    for r in rows:
        out.append(f"| {r['name']} | {r['seconds']:.4f} | {r['derived']} |")
    series = d.get("metric_series", {})
    out.append("")
    out.append(
        f"Fully-instrumented (registry + tracer + armed watchdog) vs no-op"
        f" telemetry pass: **{d.get('overhead_ratio', float('nan')):.3f}×**"
        f" (gate: ≤1.05, hard-failed by `tools/check_bench.py`); "
        f"**{d.get('recompiles', 'n/a')} serving-path recompiles** across "
        f"the armed submit/observe/page/age churn lane (gate: 0). The "
        f"instrumented pass captured {d.get('trace_events', 0)} trace "
        f"events and {sum(series.values()) if series else 0} metric "
        f"series ({series.get('counters', 0)} counters, "
        f"{series.get('gauges', 0)} gauges, "
        f"{series.get('histograms', 0)} histograms)."
    )
    return "\n".join(out)


def vecchia_rows() -> str:
    """Render BENCH_vecchia.json (the nearest-neighbor-conditioning
    trajectory) as a table + the gated claims, or a placeholder."""
    path = ROOT / "BENCH_vecchia.json"
    if not path.exists():
        return ("*(no `BENCH_vecchia.json` yet — run "
                "`PYTHONPATH=src python -m benchmarks.vecchia`)*")
    try:
        d = json.loads(path.read_text())
    except json.JSONDecodeError:
        return "*(BENCH_vecchia.json unreadable)*"
    rows = d.get("results", [])
    if not rows:
        return "*(BENCH_vecchia.json present but empty)*"
    out = ["| name | seconds | derived |", "|---|---|---|"]
    for r in rows:
        out.append(f"| {r['name']} | {r['seconds']:.4f} | {r['derived']} |")
    acc = d.get("accuracy", {})
    agree = d.get("agreement", {})
    worst = max((v for rec in agree.values() for v in rec.values()),
                default=float("nan"))
    cfg = d.get("config", {})
    out.append("")
    out.append(
        f"Clustered-spatial accuracy at N={cfg.get('n_acc', '?')}: vecchia "
        f"(k={cfg.get('k', '?')}) RMSE {acc.get('vecchia_rmse', float('nan')):.4f} vs best "
        f"global expansion ({acc.get('best_global', '?')}) "
        f"{acc.get('best_global_rmse', float('nan')):.4f} — "
        f"**{acc.get('global_over_vecchia_rmse', float('nan')):.2f}× lower error** at "
        f"**{acc.get('vecchia_over_best_global_seconds', float('nan')):.2f}×** its serve "
        f"wall-clock (gates: ≥1.0 and ≤1.25, hard-failed by "
        f"`tools/check_bench.py`).  Worst vecchia-vs-exact prediction "
        f"disagreement at k=N−1 (both kernels): **{worst:g}** (gate: ≤1e-4, "
        f"asserted in-benchmark AND gated)."
    )
    return "\n".join(out)


def table(cells, mesh: str) -> str:
    rows = [
        "| arch | shape | kind | compute s | memory s | collective s | dominant "
        "| peak GiB/dev | useful | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3,
             "fit_10k": 4, "fit_8m": 5, "predict_1m": 6}
    for c in sorted(cells, key=lambda c: (c.get("arch", ""), order.get(c.get("shape", ""), 9))):
        if c.get("mesh") != mesh:
            continue
        if "skipped" in c:
            rows.append(f"| {c['arch']} | {c['shape']} | — | SKIP (full attention "
                        f"at 500k) | | | | | | |")
            continue
        if "error" in c:
            rows.append(f"| {c['arch']} | {c['shape']} | — | ERROR | | | | | | |")
            continue
        t = c["terms"]
        peak = c["memory"].get("peak_bytes_est", 0) / 2**30
        rows.append(
            f"| {c['arch']} | {c['shape']} | {c['kind']} "
            f"| {t['compute_s']:.4f} | {t['memory_s']:.4f} "
            f"| {t['collective_s']:.4f} | **{t['dominant']}** "
            f"| {peak:.1f} | {c.get('useful_ratio', 0):.3f} "
            f"| {c.get('roofline_fraction', 0):.4f} |"
        )
    return "\n".join(rows)


def dryrun_table(cells, mesh: str) -> str:
    rows = [
        "| arch | shape | per-dev FLOPs | per-dev HBM bytes | per-dev wire bytes "
        "| dominant collectives | args GiB | temps GiB | compile s |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for c in sorted(cells, key=lambda c: (c.get("arch", ""), c.get("shape", ""))):
        if c.get("mesh") != mesh or "terms" not in c:
            continue
        pd = c["per_device"]
        colls = sorted(c["collectives"].items(), key=lambda kv: -kv[1]["wire_bytes"])
        cstr = "; ".join(f"{k}×{v['count']}" for k, v in colls[:2]) or "none"
        m = c["memory"]
        rows.append(
            f"| {c['arch']} | {c['shape']} | {pd['flops']:.2e} | {pd['bytes']:.2e} "
            f"| {pd['wire_bytes']:.2e} | {cstr} "
            f"| {m.get('argument_bytes', 0)/2**30:.2f} "
            f"| {m.get('temp_bytes', 0)/2**30:.2f} | {c.get('compile_s', 0):.0f} |"
        )
    return "\n".join(rows)


def summary(cells):
    ok = [c for c in cells if "terms" in c]
    skip = [c for c in cells if "skipped" in c]
    err = [c for c in cells if "error" in c]
    return len(ok), len(skip), len(err)


def before_after(base, now):
    """Hillclimbed cells: baseline vs final bound term."""
    def key(c):
        return (c.get("arch"), c.get("shape"), c.get("mesh"))

    bmap = {key(c): c for c in base if "terms" in c}
    rows = [
        "| cell | bound before (s) | bound after (s) | speedup | peak before → after (GiB) |",
        "|---|---|---|---|---|",
    ]
    targets = [
        ("fagp", "fit_8m", "16x16"), ("fagp", "predict_1m", "16x16"),
        ("zamba2-7b", "train_4k", "16x16"),
        ("deepseek-v3-671b", "decode_32k", "16x16"),
        ("mamba2-130m", "train_4k", "16x16"),
        ("qwen2-1.5b", "train_4k", "16x16"),
        ("qwen2.5-3b", "train_4k", "16x16"),
        ("smollm-360m", "train_4k", "16x16"),
        ("starcoder2-3b", "train_4k", "16x16"),
        ("llama-3.2-vision-11b", "train_4k", "16x16"),
    ]
    nmap = {key(c): c for c in now if "terms" in c}
    for t in targets:
        b, n = bmap.get(t), nmap.get(t)
        if not b or not n:
            continue
        bb, nb = b["terms"]["bound_s"], n["terms"]["bound_s"]
        bp = b["memory"].get("peak_bytes_est", 0) / 2**30
        np_ = n["memory"].get("peak_bytes_est", 0) / 2**30
        rows.append(f"| {t[0]}/{t[1]} | {bb:.3f} | {nb:.3f} | **{bb/nb:.1f}×** "
                    f"| {bp:.1f} → {np_:.1f} |")
    return "\n".join(rows)


def _table_or_placeholder(render, cells, mesh):
    # placeholder only when NOTHING was recorded for this mesh; cells that
    # exist but errored/skipped must still render as ERROR/SKIP rows
    if not any(c.get("mesh") == mesh for c in cells):
        return _NO_CELLS
    return render(cells, mesh)


def main():
    cells = load(DRY)
    base = load(BASE)
    n_ok, n_skip, n_err = summary(cells)
    perf_path = ROOT / "experiments" / "perf_log.md"
    perf_log = (
        perf_path.read_text() if perf_path.exists()
        else "*(no `experiments/perf_log.md` yet — hillclimb notes land "
             "there as §Perf iterations are run)*"
    )

    md = f"""# EXPERIMENTS

Reproduction + pod-scale systems build of **“Parallel Gaussian Process with
Kernel Approximation in CUDA”** (Carminati, 2024) in JAX for TPU v5e pods.
See `README.md` for the architecture and the paper→code map; this file
records the measurements.

## Reproduction vs the paper's claims

The paper's experiment (Fig. 1) times FAGP — eigensystem + posterior mean —
as n and p grow at N = 10⁴, CPU (Eigen) vs GPU (cuBLAS). Claims reproduced
here (CPU container; `python -m benchmarks.run`, see bench_output.txt):

1. **FAGP ≡ exact GP accuracy at a fraction of the cost** (the Joukov–Kulić
   foundation): identical RMSE at N=2000 with a **33× speedup**
   (`fagp_vs_exact`), growing with N exactly as O(N³) vs O(NM²) predicts.
2. **M = nᵖ blow-up** (the paper's stated limitation): visible in
   `fig1_time_vs_n_p` — e.g. p=3 fused time grows 3.2 ms → 30.9 ms from
   n=3 → n=7 (M: 27 → 343).
3. **Parallel GEMM formulation wins**: the paper's literal Eq. 11–12 GEMM
   chain (`mode="paper"`, what cuFAGP executes) vs our fused weight-space
   path on identical hardware: **6–19× fused speedup** — and on the
   production mesh the same GEMM schedule reaches the compute roofline
   (§Perf F1, fraction ≈ 1.0).
4. **Beyond the paper** — hyperbolic-cross/total-degree index sets:
   same RMSE as the full grid at p=4 with **34× fewer columns and ~160×
   less time** (`index_set_ablation`); hyperparameter learning via NLML
   gradients (the paper's declared future work, now `GP.optimize`) recovers
   the true noise to 3 decimal places (examples/hyperparam_learning.py);
   multi-output sessions share one M×M factorization across T tasks
   (`multi_output`, **6.3× over per-task fits at T=8** on this container).

All of the above run through the self-describing `GP` session facade
(`src/repro/core/gp.py`): one `GPSpec` merges the kernel hyperparameters
and the expansion choices, is baked into the state at fit time, and no call
site re-passes configuration (tests/test_gp_api.py pins the contracts).

## §Methodology (CPU-host dry-run, TPU v5e cost model)

* 512 virtual host devices (`--xla_force_host_platform_device_count=512`);
  meshes 16×16 (pod) and 2×16×16 (multi-pod). Every cell is
  `jit(...).lower().compile()` — sharding errors, layout mismatches and
  OOM-scale buffers surface exactly as they would on hardware.
* Hardware constants: **197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s ICI** per
  chip (v5e).
* `compiled.cost_analysis()` counts while-loop bodies ONCE (verified
  empirically), which would undercount every scanned layer stack by ~n_layers.
  Costs are therefore rebuilt from the compiled HLO text
  (`src/repro/roofline/hlo_cost.py`): dot/triangular-solve/cholesky FLOPs and
  collective payloads per computation, scaled by loop trip counts parsed
  from loop conditions; HBM bytes are an op-result-size proxy (fusion
  internals excluded). Known artifact: the CPU backend promotes bf16 dots
  to f32, inflating some byte/wire counts ≈2× vs a real TPU lowering —
  noted where material.
* `roofline_fraction` = (MODEL_FLOPS / peak / chips) / max(compute, memory,
  collective); MODEL_FLOPS = 6·N_active·D for LM cells (3× forward for
  train, 1× for prefill; decode counts one token), 2NM² + M³/3 for FAGP.

## §Dry-run

{n_ok} cells compiled OK, {n_skip} recorded SKIPs (long_500k × full-attention
archs — see `src/repro/configs/shapes.py`), {n_err} errors, across BOTH meshes
(16×16 = 256 chips; 2×16×16 = 512 chips, proving the 'pod' axis shards).
Per-cell JSON in `experiments/dryrun/` (baseline preserved in
`experiments/dryrun_baseline/`). Multi-pod (2×16×16) excerpt:

{_table_or_placeholder(dryrun_table, cells, "2x16x16")}

## §Roofline (single-pod 16×16, after §Perf optimizations)

{_table_or_placeholder(table, cells, "16x16")}

Reading of the dominant bottlenecks:

* **train_4k** cells are memory-term dominated on this cost model, chiefly
  saved-activation traffic; the scan-over-layers backward saves one
  (B,S,d) carry per layer, and XLA hoists a bf16→f32 convert of the whole
  stack (CPU-backend artifact ~2×). Seq-sharding the SSM residual (§Perf Z1)
  is the template fix, applied to ssm/hybrid.
* **decode** cells are memory-bound after §Perf D1 — reading the weights +
  KV/latent cache once per token is the floor; batch 128 amortizes poorly
  by construction of the assigned shape.
* **Low useful-ratio cells** (smollm 0.07, qwen2 0.16, whisper 0.06) share
  one cause: head counts (15/12/12) that do not divide the 16-way model
  axis ⇒ attention runs model-replicated. On a real deployment the mesh
  would be reshaped (e.g. 32×8); with the mesh fixed by the assignment we
  document the fraction instead.
* **fagp cells sit at fraction ≈ 1.0** (compute roofline) after §Perf F1 —
  the paper's workload is the best-mapped workload in the table, as it
  should be.

## §Perf — baseline → hillclimb results

Three cells selected per the protocol: worst roofline fraction
(deepseek-v3/decode_32k), most collective-bound (zamba2/train_4k), most
paper-representative (fagp/fit_8m + predict_1m). Summary:

{before_after(base, cells) if base and cells else _NO_CELLS}

{perf_log}

## Paper-faithful vs beyond-paper (algorithm level)

| variant | what it is | time (N=2000, p=3, n=7, CPU) |
|---|---|---|
| `mode="paper"` | literal Eq. 11–12 GEMM chain incl. N×N approximate inverse (what cuFAGP times) | 185.5 ms |
| `mode="fused"` (beyond-paper) | weight-space simplification, same math | 30.9 ms (6.0×) |
| + hyperbolic-cross (beyond-paper) | attacks the nᵖ blow-up itself | ~160× at p=4 vs full grid |

Both variants are validated equal to f32 tolerance (tests/test_fagp.py);
the roofline table above uses the optimized implementation, the baseline
numbers are preserved in `experiments/dryrun_baseline/`.

## §Streaming fused fit + online updates

`fit(backend="pallas")` runs two Pallas kernels per row chunk
(`src/repro/kernels/ops.py::fused_fit_moments`): the features kernel writes
the chunk's Phi once, the Gram kernel (`src/repro/kernels/gram.py`) adds
its moments.  A chunk holds at most M (padded) rows, so live memory is
O(M²) in N (pinned by `tests/test_streaming_fit.py`'s jaxpr sweep).  `fit_update` absorbs new
observations by a rank-k Cholesky update, O(k M²) with no pass over the
original N rows.  Measure both:

    PYTHONPATH=src python -c "from benchmarks import streaming_fit; streaming_fit.run()"
    PYTHONPATH=src python -m repro.launch.serve_gp --backend pallas

## §Fleet serving (GPBank)

The multi-tenant production shape: B independent small GPs resident on the
device as ONE stacked state (`src/repro/bank/bank.py::GPBank`), driven by
single batched executables — vmapped moments on the jnp backend, a bank
grid axis in the streaming fused kernel on the pallas backend
(`src/repro/kernels/phi_gram.py::bank_phi_gram_kernel`), and a serving
router that coalesces per-tenant queues into padded mixed-tenant
microbatches (`src/repro/bank/router.py::BankRouter`).  Batched-vs-loop
parity (≤1e-5 abs, both backends) is pinned in tests/test_gp_bank.py; the
bank-vs-loop speedup and the bank-size sweep come from:

    PYTHONPATH=src python -m benchmarks.gp_bank      # writes BENCH_gp_bank.json
    PYTHONPATH=src python -m repro.launch.serve_gp --fleet 64 --n-train 64

On this container the B=64 bank answers a mixed-tenant batch **25–36×
faster than a Python loop of single-model `mean_var` calls** over the
identical per-tenant sessions (jnp backend, run-to-run spread; ~9–10× on
pallas interpret), with identical results — the loop pays per-call
dispatch B times, the bank once, and the
bank serves variances from a per-slot B⁻¹ cache that is invalidated by
construction (every mutation returns a new immutable bank).
`BENCH_gp_bank.json` records the trajectory machine-readably; CI gates
every `BENCH_*.json` (schema + parity + timing ratios) with
`tools/check_bench.py` against the committed `BENCH_baselines.json`.

## §Asynchronous fleet serving (FleetEngine)

The serving loop itself, rebuilt as a pipeline
(`src/repro/bank/engine.py::FleetEngine`): admission with per-tenant
deadlines (expired tickets answered by the documented NaN/inf timeout
sentinel, never holding a seat in a padded block), queue-budget
backpressure at submit time, arrival-rate-driven power-of-two bucket
autotuning (up to `max_coalesce` microbatches fused per dispatch — the
bucket ladder is FIXED, so traffic churn never compiles a new serving
executable), a lean dispatch path that resolves the slot map + backend
function once per bank version, and dispatch-ahead harvesting with no
per-block barrier.  Per-tenant p50/p99 and sustained QPS come from the
engine's own `LatencyStats` (exactly `numpy.percentile`, pinned by
tests/test_serve_engine.py, alongside the property-based interleaving
and fault-injection battery):

    PYTHONPATH=src python -m benchmarks.serve_latency  # writes BENCH_serve.json
    PYTHONPATH=src python -m repro.launch.serve_gp --fleet 64 --engine pipelined

Current trajectory (acceptance shape B=64/microbatch=64; the speedup and
no-dropped-tickets claims are HARD gates in `tools/check_bench.py`):

{serve_rows()}

## §Tenant lifecycle (TieredBank)

The fleet made elastic (`src/repro/bank/lifecycle.py::TieredBank`): the
hot working set stays device-resident in a `GPBank`, everything else
lives as versioned per-tenant checkpoints
(`src/repro/checkpoint/gpstate.py` — the manifest carries the GPSpec
structure + expansion + an omega hash, so restoring into a mismatched
spec raises exactly like `with_spec`).  A cold tenant's first query
warm-restores it through the recompile-free `GPBank.insert` (LRU tenant
evicted to the cold tier); arbitrary paging churn compiles ZERO new
executables (pinned by tests/test_lifecycle.py with the same jit
cache-size mechanism as tests/test_gp_bank.py).  Sliding-window
forgetting ages drifted tenants via the batched rank-k Cholesky
*downdate* (the mirror of the rank-k update), falling back to a masked
refit on the retained window when a downdate loses positive
definiteness — `serve_fleet` wires this to `BankRouter` staleness so
drifted tenants get aged, then re-optimized:

    PYTHONPATH=src python -m benchmarks.tenant_churn  # writes BENCH_lifecycle.json
    PYTHONPATH=src python -m repro.launch.serve_gp --fleet 16 --capacity 8 \\
        --cold-dir /tmp/cold --window 40

Current trajectory (acceptance shape: 16 tenants through 8 hot slots;
paged-vs-resident and downdate-vs-refit parities are HARD gates in
`tools/check_bench.py`):

{lifecycle_rows()}

## §Sharded fleet (ShardedGPBank)

The mega-bank sharded across a device mesh
(`src/repro/bank/sharded.py::ShardedGPBank`): the stacked `FAGPState`'s
leading tenant axis splits over an S-way 'bank' mesh axis (2-D
`(bank, data)` meshes compose with the v2 row-sharded fit for large-N
tenants), and every serving / ingest / churn executable runs SHARD-LOCAL
— no cross-shard collective appears on the hot path, so per-device work
divides by S.  Tenants place round-robin at fit, least-loaded on insert;
`BankRouter.rebalance` migrates tenants off the fullest shard through the
same traced-slot executables (zero recompiles, pinned in
tests/test_shard_bank.py); `TieredBank` cold-restores land on the
least-loaded shard.  Dispatch buckets per shard, so one hot shard does
not pad-inflate the others:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.shard_scaling  # writes BENCH_shard.json
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.serve_gp --fleet 64 --shards 8

Current trajectory (acceptance shape B=1024 over a 1/2/4/8 shard sweep;
the projected-speedup, wall-overhead and parity claims are HARD gates in
`tools/check_bench.py`):

{shard_rows()}

## §Fleet telemetry (observability)

The serving stack instrumented end to end (`src/repro/obs/`, stdlib-only):
a metrics registry (counters / gauges / fixed-bucket histograms, one
Prometheus + JSON schema — `src/repro/obs/metrics.py`), Chrome-trace span
tracing over every pipeline stage (admit → coalesce → bucket-select →
dispatch → device-wait → harvest → expire, plus page-in / evict / age /
downdate / checkpoint and hyperopt progress — `src/repro/obs/trace.py`),
and a recompile watchdog that promotes the test suite's jit cache-size
idiom to a production guard over the sixteen serving-path executables (including the seven shard-local ones)
(`src/repro/obs/watchdog.py`).  Telemetry is strictly opt-in: every layer
defaults to no-op implementations whose record paths allocate NOTHING
(pinned with `tracemalloc` in tests/test_obs.py), and the fully-ON cost
is measured as its own benchmark lane:

    PYTHONPATH=src python -m benchmarks.serve_latency  # writes BENCH_obs.json too
    PYTHONPATH=src python -m repro.launch.serve_gp --fleet 64 \\
        --metrics-port 0 --trace-out trace.jsonl --watchdog warn

Current trajectory (overhead measured at the serving acceptance shape via
interleaved instrumented/null pairs; both claims are HARD gates in
`tools/check_bench.py`, and `tools/check_trace.py` validates the emitted
JSONL in CI):

{obs_rows()}

## §Hyperparameter optimization at fleet scale

The paper's declared future work ("a parallel implementation of the
optimization problem for hyperparameter learning"), taken to the fleet:
`GPBank.optimize` / `GP.optimize(..., restarts=R)` run ONE lane engine
(`src/repro/optim/gp_hyperopt.py`) over a (B tenants × R restarts)
parameter stack — one compiled AdamW step per iteration for the whole
fleet, per-restart convergence masks (frozen lanes stop moving bit-exactly,
zero recompiles), best-restart selection by final NLML, and a batched refit
of the winners into the stacked bank state (per-slot hyperparameters — the
bank becomes heterogeneous and serves each tenant under its own learned
values).  The NLML objective streams its moments through the backend
registry, so optimization never materializes the N×M feature matrix on
either backend (jaxpr sweep in `tests/test_gp_hyperopt.py`).  Per-tenant
lane math is bit-identical to a single-model run by construction, so the
benchmark ASSERTS ≤1e-5 parity in selected hyperparameters and NLML
against a Python loop of `GP.optimize` runs:

    PYTHONPATH=src python -m benchmarks.gp_hyperopt   # writes BENCH_optimize.json

Current trajectory (acceptance config B=64/R=4 on the jnp backend; pallas
runs reduced on CPU interpret):

{optimize_rows()}

## §Multi-output sessions

The first workload the session redesign unlocks: `GP.fit(X, Y, spec)` with
Y of shape (N, T) runs the streaming moment pass and the O(M³) Cholesky
once and solves the T mean-weight systems against the shared factor in one
batched triangular solve (per-task weights u of shape (M, T)).  Numerics
are pinned to agree with T independent fits to f32 tolerance
(tests/test_gp_api.py); the shared fraction of the per-task FLOPs and the
measured speedup come from:

    PYTHONPATH=src python -c "from benchmarks import multi_output; multi_output.run()"

## §Kernel expansions (capability × family matrix)

The expansion layer (`src/repro/core/expansions.py`) turns every capability
above — streaming fused fit, incremental update, multi-output, distributed
schedules, fleet banks — into a capability × kernel-family matrix: the
Hermite–Mercer eigen-expansion (the paper's), RFF–SE, and RFF–Matérn-5/2
all run through the same `GP`/`GPBank` entry points on both backends, with
the pallas streaming path pinned (jaxpr sweep, `tests/test_streaming_fit.py`)
to never materialize the N×M Phi for ANY of them.  Reconstruction bounds
(`tests/test_expansions.py`): geometric truncation for Hermite, Monte-Carlo
4/√R for the RFF families.  On this container RFF–Matérn-5/2 at M=100
matches the exact Matérn GP's RMSE at N=2000 with a **~60× speedup**
(`fagp_vs_exact --expansion rff_matern52`), and an RFF bank serves
mixed-tenant batches just like a Hermite one.  Numbers:

    PYTHONPATH=src python -m benchmarks.kernel_micro --expansion all
    PYTHONPATH=src python -m benchmarks.fagp_vs_exact --expansion all
    PYTHONPATH=src python -m benchmarks.gp_bank --expansion all

Current `BENCH_expansions.json` trajectory (merged rows; CI smoke keeps the
schema valid):

{expansion_rows()}

## §Vecchia (nearest-neighbor conditioning)

The second APPROXIMATION FAMILY behind the `GP` facade
(`src/repro/core/vecchia.py`, conditioning sets from the blocked streaming
top-k in `src/repro/kernels/knn.py`): where FAGP replaces the N×N kernel
inverse by a global low-rank feature system, Vecchia factorizes along the
data ordering and truncates every conditional to the k nearest points —
batched (B, k, k) Cholesky lanes, O(N·k³), never a Q×N distance matrix
(jaxpr sweep in `tests/test_vecchia.py`, same methodology as the streaming
fit).  `spec.approximation` selects the family through the
`core.approximation` protocol; capability refusals (vecchia has no
`predict`/`optimize`, fagp entry points refuse vecchia specs, `GPBank`
declines both ways) raise the structured `UnsupportedError` with
`(layer, capability, spec)`.  Convergence to `exact_gp` as k→N is pinned
for both reference kernels; the clustered short-lengthscale regime where
the locality wins is the benchmark:

    PYTHONPATH=src python -m benchmarks.vecchia   # writes BENCH_vecchia.json

Current trajectory (accuracy + agreement claims are HARD gates in
`tools/check_bench.py`):

{vecchia_rows()}

## §Regenerating

This file is GENERATED — edit `benchmarks/gen_experiments.py`, not this
file.  Full pipeline on a fresh checkout:

    # 1. dry-run every (arch × shape × mesh) cell -> experiments/dryrun/*.json
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
    # 2. (optional) preserve a baseline for §Perf before/after tables
    cp -r experiments/dryrun experiments/dryrun_baseline
    # 3. CPU benchmark CSVs referenced in the claims section
    PYTHONPATH=src python -m benchmarks.run
    # 4. render this file
    PYTHONPATH=src python -m benchmarks.gen_experiments

Sections stay structurally present with placeholder markers when their
input data is missing, so docs references remain stable.
"""
    (ROOT / "EXPERIMENTS.md").write_text(md)
    print(f"EXPERIMENTS.md written: ok={n_ok} skip={n_skip} err={n_err}")


if __name__ == "__main__":
    main()
