"""Property tests for error-feedback int8 gradient compression."""
import numpy as np
import jax
import jax.numpy as jnp
from hypcompat import given, settings, st  # hypothesis, or fixed examples

from repro.parallel import compress


class TestQuantize:
    @given(seed=st.integers(0, 50), scale=st.floats(1e-4, 1e3))
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_error_bounded(self, seed, scale):
        rng = np.random.default_rng(seed)
        x = jnp.asarray((rng.standard_normal(1000) * scale).astype(np.float32))
        q, s = compress.quantize(x)
        y = compress.dequantize(q, s, x.shape)
        # per-block error <= blockmax/127/2 (round-to-nearest)
        blocks = np.pad(np.asarray(x), (0, (-1000) % compress.BLOCK)).reshape(-1, compress.BLOCK)
        bound = np.abs(blocks).max(axis=1, keepdims=True) / 127.0
        err = np.abs(np.pad(np.asarray(x - y), (0, (-1000) % compress.BLOCK)).reshape(-1, compress.BLOCK))
        assert np.all(err <= bound * 0.51 + 1e-9)

    def test_error_feedback_unbiased_over_steps(self):
        """Constant gradient + error feedback: mean applied update -> g."""
        g = jnp.asarray(np.linspace(-3e-3, 7e-3, 512).astype(np.float32))
        r = jnp.zeros_like(g)
        applied = []
        for _ in range(50):
            v = g + r
            q, s = compress.quantize(v)
            deq = compress.dequantize(q, s, g.shape)
            r = v - deq
            applied.append(np.asarray(deq))
        mean_applied = np.mean(applied, axis=0)
        np.testing.assert_allclose(mean_applied, np.asarray(g), atol=5e-6)

    def test_exactness_for_zero(self):
        q, s = compress.quantize(jnp.zeros((64,)))
        assert float(jnp.abs(compress.dequantize(q, s, (64,))).max()) == 0.0


class TestCompressedAllReduce:
    def test_matches_mean_of_shards(self):
        """On a 1-device mesh the compressed all-reduce == dequantized value;
        residual carries the quantization error."""
        mesh = jax.make_mesh((1,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((128,)).astype(np.float32))}
        state = compress.CompressionState.init(g)

        def run(g, r):
            return compress.compress_allreduce(g, compress.CompressionState(r), "pod")

        with jax.set_mesh(mesh):
            out, new_state = jax.shard_map(
                run, mesh=mesh,
                in_specs=(jax.sharding.PartitionSpec(),) * 2,
                out_specs=(jax.sharding.PartitionSpec(),) * 2,
                check_vma=False,
            )(g, state.residual)
        q, s = compress.quantize(g["w"])
        expect = compress.dequantize(q, s, g["w"].shape)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(expect), atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(new_state.residual["w"]),
            np.asarray(g["w"] - expect), atol=1e-7,
        )
