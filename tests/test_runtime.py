"""Fault-tolerance tests: checkpoint roundtrip, restart-exactness,
preemption handling, async checkpointing, optimizer behavior."""
import os
import signal
import threading

import numpy as np
import jax
import jax.numpy as jnp

from repro import checkpoint, optim
from repro.data import TokenStream
from repro.runtime import TrainLoopConfig, train_loop


def _tiny_problem(seed=0):
    """2-layer MLP regression on a fixed function: fast, deterministic."""
    key = jax.random.key(seed)
    k1, k2 = jax.random.split(key)
    params = {
        "w1": jax.random.normal(k1, (8, 32)) * 0.3,
        "w2": jax.random.normal(k2, (32, 1)) * 0.3,
        "b": jnp.zeros((1,)),
    }
    ocfg = optim.AdamWConfig(lr=1e-2, weight_decay=0.0)

    def batch_fn(step):
        rng = np.random.default_rng(step)
        x = rng.standard_normal((16, 8)).astype(np.float32)
        y = np.sin(x.sum(axis=1, keepdims=True)).astype(np.float32)
        return {"x": jnp.asarray(x), "y": jnp.asarray(y)}

    def loss_fn(p, b):
        h = jnp.tanh(b["x"] @ p["w1"])
        pred = h @ p["w2"] + p["b"]
        l = jnp.mean((pred - b["y"]) ** 2)
        return l, {"loss": l}

    @jax.jit
    def step_fn(p, o, b):
        (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b)
        p, o, om = optim.apply_updates(p, g, o, ocfg)
        return p, o, {**m, **om}

    return params, optim.init(params, ocfg), step_fn, batch_fn


class TestCheckpoint:
    def test_roundtrip_bf16_and_nested(self, tmp_path):
        tree = {
            "a": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "nested": {"b": jnp.ones((2, 2), jnp.float32), "step": jnp.asarray(7)},
        }
        checkpoint.save(tmp_path, 3, tree)
        step, out = checkpoint.restore(tmp_path, tree)
        assert step == 3
        assert out["a"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out["a"], np.float32),
                                      np.asarray(tree["a"], np.float32))
        np.testing.assert_array_equal(np.asarray(out["nested"]["b"]),
                                      np.asarray(tree["nested"]["b"]))

    def test_latest_and_atomicity(self, tmp_path):
        tree = {"w": jnp.zeros((4,))}
        checkpoint.save(tmp_path, 1, tree)
        checkpoint.save(tmp_path, 5, tree)
        assert checkpoint.latest_step(tmp_path) == 5
        # a stale tmp dir must not break anything
        (tmp_path / "tmp.9.123").mkdir()
        assert checkpoint.latest_step(tmp_path) == 5

    def test_async_checkpointer(self, tmp_path):
        c = checkpoint.AsyncCheckpointer(tmp_path)
        c.save(10, {"w": jnp.ones((128, 128))})
        c.wait()
        step, out = checkpoint.restore(tmp_path, {"w": jnp.zeros((128, 128))})
        assert step == 10 and float(out["w"][0, 0]) == 1.0


class TestTrainLoop:
    def test_loss_decreases(self, tmp_path):
        params, opt, step_fn, batch_fn = _tiny_problem()
        cfg = TrainLoopConfig(steps=300, ckpt_every=1000, ckpt_dir=None,
                              log_every=50, handle_signals=False)
        _, _, rep = train_loop(step_fn, params, opt, batch_fn, cfg,
                               log_fn=lambda s: None)
        assert rep["history"][-1]["loss"] < rep["history"][0]["loss"] * 0.8

    def test_restart_is_exact(self, tmp_path):
        """Run 60 steps straight vs 30 + crash + resume 30: same params."""
        params, opt, step_fn, batch_fn = _tiny_problem()
        cfg_a = TrainLoopConfig(steps=60, ckpt_every=1000, ckpt_dir=None,
                                log_every=100, handle_signals=False)
        pa, _, _ = train_loop(step_fn, params, opt, batch_fn, cfg_a,
                              log_fn=lambda s: None)

        d = tmp_path / "ck"
        cfg_b1 = TrainLoopConfig(steps=30, ckpt_every=30, ckpt_dir=str(d),
                                 log_every=100, handle_signals=False,
                                 async_ckpt=False)
        train_loop(step_fn, params, opt, batch_fn, cfg_b1, log_fn=lambda s: None)
        # "crash": fresh process state; loop must restore step 30 checkpoint
        cfg_b2 = TrainLoopConfig(steps=60, ckpt_every=1000, ckpt_dir=str(d),
                                 log_every=100, handle_signals=False,
                                 async_ckpt=False)
        pb, _, rep = train_loop(step_fn, params, opt, batch_fn, cfg_b2,
                                log_fn=lambda s: None)
        assert rep["final_step"] == 60
        for ka in pa:
            np.testing.assert_allclose(
                np.asarray(pa[ka]), np.asarray(pb[ka]), rtol=1e-6, atol=1e-7
            )

    def test_preemption_checkpoints_and_exits(self, tmp_path):
        params, opt, step_fn, batch_fn = _tiny_problem()
        d = tmp_path / "ck"
        cfg = TrainLoopConfig(steps=10_000, ckpt_every=10_000, ckpt_dir=str(d),
                              log_every=10_000, handle_signals=True,
                              async_ckpt=False)

        def fire():
            os.kill(os.getpid(), signal.SIGTERM)

        t = threading.Timer(1.0, fire)
        t.start()
        _, _, rep = train_loop(step_fn, params, opt, batch_fn, cfg,
                               log_fn=lambda s: None)
        t.join()
        assert rep["preempted"]
        assert rep["final_step"] < 10_000
        assert checkpoint.latest_step(d) == rep["final_step"]

    def test_elastic_restore_resharding(self, tmp_path):
        """Checkpoint written unsharded restores onto a live mesh sharding."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = {"w": jnp.arange(16.0).reshape(4, 4)}
        checkpoint.save(tmp_path, 1, params)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        step, out = checkpoint.restore(tmp_path, params, shardings=sh)
        assert out["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(params["w"]))


class TestOptim:
    def test_adamw_converges_quadratic(self):
        p = {"x": jnp.asarray([5.0, -3.0])}
        cfg = optim.AdamWConfig(lr=0.1, weight_decay=0.0, clip_norm=None)
        s = optim.init(p, cfg)
        for _ in range(500):
            g = jax.grad(lambda p: jnp.sum((p["x"] - 1.0) ** 2))(p)
            p, s, _ = optim.apply_updates(p, g, s, cfg)
        np.testing.assert_allclose(np.asarray(p["x"]), [1.0, 1.0], atol=2e-2)

    def test_clip_norm_bounds_update(self):
        p = {"x": jnp.zeros((4,))}
        cfg = optim.AdamWConfig(lr=1.0, clip_norm=1e-3, weight_decay=0.0)
        s = optim.init(p, cfg)
        g = {"x": jnp.full((4,), 1e6)}
        _, _, m = optim.apply_updates(p, g, s, cfg)
        assert float(m["grad_norm"]) > 1e5  # reported pre-clip

    def test_bf16_state_dtype(self):
        p = {"x": jnp.zeros((4,), jnp.bfloat16)}
        cfg = optim.AdamWConfig(lr=1e-3, state_dtype="bfloat16")
        s = optim.init(p, cfg)
        assert s["mu"]["x"]["m"].dtype == jnp.bfloat16

    def test_data_stream_deterministic(self):
        s1 = TokenStream(vocab=100, seq=16, global_batch=4, seed=1)
        s2 = TokenStream(vocab=100, seq=16, global_batch=4, seed=1)
        np.testing.assert_array_equal(
            np.asarray(s1.batch(7)["tokens"]), np.asarray(s2.batch(7)["tokens"])
        )
        assert not np.array_equal(
            np.asarray(s1.batch(7)["tokens"]), np.asarray(s1.batch(8)["tokens"])
        )


class TestCompileCache:
    """The entry points' persistent compilation cache
    (``launch/compile_cache.py``): JAX's own directory when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else ``<checkout>/.jax_cache``."""

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path):
        from repro.launch.compile_cache import enable_compile_cache

        was = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was

    def test_default_is_the_checkout(self, monkeypatch):
        from pathlib import Path

        from repro.launch.compile_cache import enable_compile_cache

        was = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            got = enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        assert Path(got) == Path(__file__).resolve().parents[1] / ".jax_cache"
