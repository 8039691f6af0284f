import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (no mismatched collectives),
  * the per-device memory footprint (memory_analysis),
  * the FLOP/byte/collective composition (cost_analysis + HLO parse),
and records a JSON blob under experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch fagp --shape fit_8m
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro import optim
from repro.configs import ARCHS, fagp as fagp_cfg
from repro.configs.shapes import SHAPES, input_specs, supports
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import get_model
from repro.parallel import hints, sharding
from repro.roofline import analyze_compiled

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _tokens_of(shape_name: str) -> int:
    s = SHAPES[shape_name]
    return s.batch * (s.seq if s.kind in ("train", "prefill") else 1)


def run_lm_cell(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = ARCHS[arch_id].CONFIG
    if not supports(cfg, shape_name):
        return {"skipped": "long_500k requires sub-quadratic context handling; "
                           f"{arch_id} is full-attention (DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    model = get_model(cfg)
    spec = SHAPES[shape_name]

    params_av = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    p_sh = sharding.param_shardings(
        params_av, cfg, mesh, serving=spec.kind != "train"
    )

    t0 = time.time()
    with jax.set_mesh(mesh), hints.activate(mesh):
        if spec.kind == "train":
            ocfg = optim.AdamWConfig(lr=1e-4, state_dtype="bfloat16")
            opt_av = jax.eval_shape(lambda: optim.init(params_av, ocfg))
            o_sh = sharding.opt_state_shardings(opt_av, params_av, cfg, mesh)
            batch = input_specs(cfg, shape_name)
            b_sh = sharding.batch_shardings(batch, mesh)
            step = make_train_step(model, ocfg)
            lowered = jax.jit(
                step, in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            ).lower(params_av, opt_av, batch)
        elif spec.kind == "prefill":
            batch = input_specs(cfg, shape_name)
            b_sh = sharding.batch_shardings(batch, mesh)
            cache_av = jax.eval_shape(lambda: model.init_cache(spec.batch, spec.seq))
            c_sh = sharding.cache_shardings(cache_av, cfg, mesh)
            step = make_prefill_step(model)
            lowered = jax.jit(
                step, in_shardings=(p_sh, b_sh), out_shardings=(None, c_sh),
            ).lower(params_av, batch)
        else:  # decode
            batch, cache_av = input_specs(cfg, shape_name)
            b_sh = sharding.batch_shardings(batch, mesh)
            c_sh = sharding.cache_shardings(cache_av, cfg, mesh)
            step = make_decode_step(model)
            lowered = jax.jit(
                step, in_shardings=(p_sh, b_sh, c_sh),
                out_shardings=(None, c_sh), donate_argnums=(2,),
            ).lower(params_av, batch, cache_av)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    tokens = _tokens_of(shape_name)
    n_active = cfg.active_param_count()
    model_flops = (3 if spec.kind == "train" else 1) * 2.0 * n_active * tokens
    rec = analyze_compiled(compiled, n_chips, model_flops=model_flops)
    rec.update(
        arch=arch_id, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        kind=spec.kind, tokens=tokens,
        params_total=cfg.param_count(), params_active=n_active,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
    )
    return rec


def run_fagp_cell(shape_name: str, multi_pod: bool) -> dict:
    from repro.core import distributed as dgp

    wl = fagp_cfg.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    with jax.set_mesh(mesh), hints.activate(mesh):
        if wl.kind == "fit":
            lowered = dgp.lower_fit(wl, mesh)
        else:
            lowered = dgp.lower_predict(wl, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    M = wl.cfg.indices(wl.p).shape[0]
    # useful FLOPs: fit = 2 N M^2 (Gram) + (2/3) M^3 (Cholesky) + phi build;
    # predict = 2 N M^2 (solve+var) + 2 N M (mean)
    if wl.kind == "fit":
        model_flops = 2.0 * wl.N * M * M + (2.0 / 3.0) * M**3
    else:
        model_flops = 2.0 * wl.N * M * M + 2.0 * wl.N * M
    rec = analyze_compiled(compiled, n_chips, model_flops=model_flops)
    rec.update(
        arch="fagp", shape=shape_name, mesh="2x16x16" if multi_pod else "16x16",
        kind=wl.kind, N=wl.N, p=wl.p, M=int(M),
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
    )
    return rec


def run_cell(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    try:
        if arch_id == "fagp":
            return run_fagp_cell(shape_name, multi_pod)
        return run_lm_cell(arch_id, shape_name, multi_pod)
    except Exception as e:  # a failure here is a bug in the system
        return {
            "arch": arch_id, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    archs = list(ARCHS) + ["fagp"] if args.arch == "all" else args.arch.split(",")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_err = 0
    for arch_id in archs:
        shape_names = (
            list(fagp_cfg.SHAPES) if arch_id == "fagp" else list(SHAPES)
        ) if args.shape == "all" else args.shape.split(",")
        for shape_name in shape_names:
            for multi_pod in meshes:
                mesh_tag = "2x16x16" if multi_pod else "16x16"
                cell = f"{arch_id}__{shape_name}__{mesh_tag}"
                t0 = time.time()
                rec = run_cell(arch_id, shape_name, multi_pod)
                dt = time.time() - t0
                (out / f"{cell}.json").write_text(json.dumps(rec, indent=1))
                if "error" in rec:
                    n_err += 1
                    status = "ERROR " + rec["error"][:120]
                elif "skipped" in rec:
                    n_skip += 1
                    status = "SKIP"
                else:
                    n_ok += 1
                    t = rec["terms"]
                    status = (
                        f"ok  dom={t['dominant']:<10} "
                        f"c/m/coll(ms)={1e3*t['compute_s']:.2f}/"
                        f"{1e3*t['memory_s']:.2f}/{1e3*t['collective_s']:.2f} "
                        f"peakGB={rec['memory'].get('peak_bytes_est', 0)/2**30:.2f}"
                    )
                print(f"[{dt:7.1f}s] {cell:<55} {status}", flush=True)
    print(f"\nDONE ok={n_ok} skip={n_skip} err={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
