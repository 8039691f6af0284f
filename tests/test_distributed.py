"""Distribution-correctness tests on 8 virtual devices (subprocess-isolated:
XLA device count is locked at first jax init, so each test body runs in its
own python with XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


class TestDistributedFAGP:
    def test_fit_distributed_matches_single(self):
        run_sub("""
            import jax, numpy as np, jax.numpy as jnp
            from repro.core import fagp, mercer, distributed as dgp
            from repro.data import make_gp_dataset
            from repro.launch.mesh import make_local_mesh

            X, y, Xs, ys = make_gp_dataset(512, 2, seed=0)
            spec = fagp.GPSpec.create(8, eps=[0.8, 0.8], rho=2.0, noise=0.05)
            st = fagp.fit(X, y, spec)
            mu_ref, var_ref = fagp.predict_mean_var(st, Xs)

            mesh = make_local_mesh(data=2, model=4)
            dst = dgp.fit_distributed(X, y, spec, mesh)
            np.testing.assert_allclose(np.asarray(dst.u), np.asarray(st.u),
                                       rtol=5e-3, atol=1e-4)
            mu, var = dgp.predict_distributed(Xs, dst, mesh)
            np.testing.assert_allclose(np.asarray(mu), np.asarray(mu_ref),
                                       rtol=1e-3, atol=1e-4)
            np.testing.assert_allclose(np.asarray(var), np.asarray(var_ref),
                                       rtol=5e-3, atol=1e-6)
            # the distributed state is a full session: serving entry points
            # accept it directly, nothing re-passed
            mu2, var2 = fagp.predict_mean_var(dst, Xs)
            np.testing.assert_allclose(np.asarray(mu2), np.asarray(mu_ref),
                                       rtol=1e-3, atol=1e-4)
            print("OK fit_distributed")
        """)

    def test_collectives_present_in_fit_hlo(self):
        """The distributed fit must actually contain the M x M all-reduce."""
        run_sub("""
            import jax
            from repro.configs import fagp as fcfg
            from repro.core import distributed as dgp
            from repro.core.fagp import FAGPConfig
            from repro.launch.mesh import make_local_mesh
            from repro.parallel import hints
            import dataclasses

            wl = dataclasses.replace(
                fcfg.SHAPES["fit_10k"], N=4096, p=2,
                cfg=FAGPConfig(n=6, store_train=False))
            mesh = make_local_mesh(data=2, model=4)
            with jax.set_mesh(mesh), hints.activate(mesh):
                txt = dgp.lower_fit(wl, mesh).compile().as_text()
            assert "all-reduce" in txt, "expected Gram all-reduce in HLO"
            print("OK collectives")
        """)


class TestDistributedTrainStep:
    @pytest.mark.parametrize("arch_id", ["smollm-360m", "olmoe-1b-7b", "mamba2-130m"])
    def test_sharded_train_step_matches_single_device(self, arch_id):
        run_sub(f"""
            import dataclasses, numpy as np, jax, jax.numpy as jnp
            from repro.configs import ARCHS
            from repro.models import get_model
            from repro.parallel import hints, sharding
            from repro.launch.mesh import make_local_mesh
            from repro.launch.steps import make_train_step
            from repro import optim

            cfg = ARCHS["{arch_id}"].SMOKE
            # make dims divide the small mesh (model axis = 2)
            model = get_model(cfg)
            params = model.init_params(jax.random.key(0))
            ocfg = optim.AdamWConfig(lr=1e-3)
            opt = optim.init(params, ocfg)
            rng = np.random.default_rng(0)
            batch = {{"tokens": jnp.asarray(
                rng.integers(0, cfg.vocab, size=(8, 64)), jnp.int32)}}

            step = make_train_step(model, ocfg)
            p1, o1, m1 = jax.jit(step)(params, opt, batch)

            mesh = make_local_mesh(data=4, model=2)
            p_sh = sharding.param_shardings(params, cfg, mesh)
            o_sh = sharding.opt_state_shardings(opt, params, cfg, mesh)
            b_sh = sharding.batch_shardings(batch, mesh)
            with jax.set_mesh(mesh), hints.activate(mesh):
                params_d = jax.device_put(params, p_sh)
                opt_d = jax.device_put(opt, o_sh)
                batch_d = jax.device_put(batch, b_sh)
                p2, o2, m2 = jax.jit(
                    step, in_shardings=(p_sh, o_sh, b_sh),
                    out_shardings=(p_sh, o_sh, None),
                )(params_d, opt_d, batch_d)

            l1, l2 = float(m1["loss"]), float(m2["loss"])
            assert abs(l1 - l2) < 5e-2 * max(1.0, abs(l1)), (l1, l2)
            # spot-check a parameter after one update
            fa = jax.tree_util.tree_leaves(p1)[0]
            fb = jax.tree_util.tree_leaves(p2)[0]
            np.testing.assert_allclose(
                np.asarray(fa, np.float32), np.asarray(fb, np.float32),
                rtol=5e-2, atol=5e-3)
            print("OK", l1, l2)
        """)

    def test_decode_step_sharded_cache(self):
        run_sub("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.configs import ARCHS
            from repro.models import get_model
            from repro.parallel import hints, sharding
            from repro.launch.mesh import make_local_mesh

            cfg = ARCHS["qwen2-1.5b"].SMOKE
            model = get_model(cfg)
            params = model.init_params(jax.random.key(0))
            B, S = 8, 32
            cache = model.init_cache(B, S)
            batch = {"token": jnp.zeros((B, 1), jnp.int32),
                     "pos": jnp.asarray(3, jnp.int32)}
            logits_ref, _ = jax.jit(model.decode_step)(params, batch, cache)

            mesh = make_local_mesh(data=4, model=2)
            p_sh = sharding.param_shardings(params, cfg, mesh)
            c_sh = sharding.cache_shardings(cache, cfg, mesh)
            b_sh = sharding.batch_shardings(batch, mesh)
            with jax.set_mesh(mesh), hints.activate(mesh):
                out = jax.jit(model.decode_step,
                              in_shardings=(p_sh, b_sh, c_sh),
                              out_shardings=(None, c_sh))(
                    jax.device_put(params, p_sh),
                    jax.device_put(batch, b_sh),
                    jax.device_put(cache, c_sh))
            np.testing.assert_allclose(
                np.asarray(out[0], np.float32), np.asarray(logits_ref, np.float32),
                rtol=2e-2, atol=2e-2)
            print("OK decode")
        """)


class TestServeModeMoE:
    def test_serve_mode_matches_dense(self):
        """Tiny-T (decode) path: sharded weights + token slicing must equal
        the dense reference bit-for-bit (modulo f32 reduction order)."""
        run_sub("""
            import dataclasses, numpy as np, jax, jax.numpy as jnp
            from repro.models import moe as M
            from repro.models.config import ModelConfig
            from repro.parallel import hints
            from repro.launch.mesh import make_local_mesh

            cfg = ModelConfig(
                arch_id="t", family="moe", n_layers=1, d_model=64, n_heads=4,
                n_kv_heads=4, d_ff=32, vocab=64, n_experts=8, top_k=2,
                d_expert=32, n_shared_experts=1, capacity_factor=8.0, fsdp=True)
            p = M.moe_init(jax.random.key(0), cfg, jnp.float32)
            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32))
            y_ref, _ = M.moe_apply(p, x, cfg)
            mesh = make_local_mesh(data=2, model=4)
            with hints.activate(mesh), jax.set_mesh(mesh):
                T_l = 16 // 2
                assert (T_l * cfg.top_k) // cfg.n_experts <= 64  # serve mode on
                y_s, _ = jax.jit(lambda p, x: M.moe_apply_sharded(p, x, cfg))(p, x)
            np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_ref),
                                       rtol=2e-5, atol=2e-5)
            print("OK serve-mode moe")
        """)


class TestPipeline:
    def test_gpipe_matches_sequential(self):
        """4-stage pipeline over 'model' == sequential stage application."""
        run_sub("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.parallel.pipeline import gpipe
            from repro.launch.mesh import make_local_mesh

            S, M, mb, d = 4, 8, 4, 32
            rng = np.random.default_rng(0)
            W = jnp.asarray(rng.standard_normal((S, d, d)).astype(np.float32) / np.sqrt(d))
            b = jnp.asarray(rng.standard_normal((S, d)).astype(np.float32) * 0.1)
            x = jnp.asarray(rng.standard_normal((M, mb, d)).astype(np.float32))

            def stage(p, x):
                return jnp.tanh(x @ p["w"] + p["b"])

            params = {"w": W, "b": b}
            # sequential reference
            y_ref = x
            for s in range(S):
                y_ref = jnp.tanh(y_ref @ W[s] + b[s])

            mesh = make_local_mesh(data=2, model=4)
            with jax.set_mesh(mesh):
                y = gpipe(stage, params, x, mesh, axis="model")
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       rtol=2e-5, atol=2e-5)
            print("OK gpipe fwd")
        """)

    def test_gpipe_differentiable(self):
        run_sub("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.parallel.pipeline import gpipe
            from repro.launch.mesh import make_local_mesh

            S, M, mb, d = 4, 4, 2, 16
            rng = np.random.default_rng(1)
            W = jnp.asarray(rng.standard_normal((S, d, d)).astype(np.float32) / np.sqrt(d))
            x = jnp.asarray(rng.standard_normal((M, mb, d)).astype(np.float32))
            mesh = make_local_mesh(data=2, model=4)

            def stage(p, xin):
                return jnp.tanh(xin @ p)

            def loss_pp(W):
                y = gpipe(stage, W, x, mesh, axis="model")
                return jnp.sum(y ** 2)

            def loss_seq(W):
                y = x
                for s in range(S):
                    y = jnp.tanh(y @ W[s])
                return jnp.sum(y ** 2)

            with jax.set_mesh(mesh):
                g_pp = jax.grad(loss_pp)(W)
            g_seq = jax.grad(loss_seq)(W)
            np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                                       rtol=1e-4, atol=1e-5)
            print("OK gpipe grad")
        """)


class TestElasticScaling:
    def test_resume_on_bigger_mesh(self, tmp_path):
        """Train on 1 device, checkpoint, resume the SAME run on an 8-device
        mesh: the loop restores, reshards, and continues — elastic scaling
        end-to-end."""
        ckpt = tmp_path / "ck"
        body = f"""
            import numpy as np, jax, jax.numpy as jnp
            from repro.configs import ARCHS
            from repro.models import get_model
            from repro.parallel import hints, sharding
            from repro.launch.mesh import make_local_mesh
            from repro.launch.steps import make_train_step
            from repro.runtime import TrainLoopConfig, train_loop
            from repro.data import TokenStream
            from repro import optim

            cfg = ARCHS["smollm-360m"].SMOKE
            model = get_model(cfg)
            params = model.init_params(jax.random.key(0))
            ocfg = optim.AdamWConfig(lr=1e-3)
            opt = optim.init(params, ocfg)
            stream = TokenStream(vocab=cfg.vocab, seq=32, global_batch=8, seed=0)

            n_dev = len(jax.devices())
            if n_dev == 1:
                step = jax.jit(make_train_step(model, ocfg))
                sh = None
                ctx = None
            else:
                mesh = make_local_mesh(data=4, model=2)
                p_sh = sharding.param_shardings(params, cfg, mesh)
                o_sh = sharding.opt_state_shardings(opt, params, cfg, mesh)
                params = jax.device_put(params, p_sh)
                opt = jax.device_put(opt, o_sh)
                step = jax.jit(make_train_step(model, ocfg),
                               in_shardings=(p_sh, o_sh, None),
                               out_shardings=(p_sh, o_sh, None))
                sh = (p_sh, o_sh)

            loop = TrainLoopConfig(steps=STEPS, ckpt_every=10, log_every=100,
                                   ckpt_dir={str(ckpt)!r}, handle_signals=False,
                                   async_ckpt=False)
            p, o, rep = train_loop(step, params, opt, lambda s: stream.batch(s),
                                   loop, shardings=sh, log_fn=lambda s: None)
            print("FINAL", rep["final_step"], rep["history"][-1]["loss"])
        """
        # phase 1: single device, 10 steps
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        r1 = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(body.replace("STEPS", "10"))],
            capture_output=True, text=True, timeout=420, env=env)
        assert r1.returncode == 0, r1.stdout + r1.stderr[-2000:]
        assert "FINAL 10" in r1.stdout
        # phase 2: resume same ckpt dir on 8 virtual devices to step 20
        out = run_sub(body.replace("STEPS", "20"))
        assert "FINAL 20" in out
