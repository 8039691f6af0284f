"""Compile every Pallas kernel of the main path for a described TPU v5e.

Interpret mode (the CPU tests) cannot see what the chip's compiler
refuses: a block not aligned to the (8, 128) tiling, more VMEM than a
kernel may use, a lowering Mosaic does not implement.  These tests hand
the raw kernels (``interpret=False``; the ``ops`` wrappers would pick
interpret mode from the CPU backend) to the TPU compiler for one chip of
a described ``v5e:2x2`` topology, at a small width and at the paper's
width (p=4, n=11: M=14641 padded to 14848, N=10240), and check that the
compiled program holds the kernel.  Nothing runs; no chip is needed.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.diag_quad import diag_quad_kernel
from repro.kernels.gram import scaled_gram_kernel
from repro.kernels.hermite_phi import hermite_phi_kernel, phi_tile
from repro.kernels.phi_gram import bank_phi_gram_kernel, phi_gram_kernel
from repro.kernels.rff_phi import rff_tile

# (p, n_max, M, N): M and N already padded to the kernels' block multiples
WIDTHS = {
    "small": (2, 8, 256, 2048),
    "paper": (4, 11, 14848, 10240),
}
BANK = {"small": 4, "paper": 2}      # tenants in the bank kernel's grid


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a program compiled for a described chip is written to the
    cache but cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]


def _kernel_call(name: str, width: str, sharding):
    """(kernel with its static arguments bound, argument shapes)."""
    p, n, M, N = WIDTHS[width]
    if name == "phi_gram_hermite":
        fn = functools.partial(phi_gram_kernel, n_max=n, interpret=False,
                               tile_fn=phi_tile)
        shapes = ((p, N), (p, 3), (p * n, M), (1, M), (1, 1), (1, N), (1, N))
    elif name == "phi_gram_rff":
        fn = functools.partial(phi_gram_kernel, n_max=n, interpret=False,
                               tile_fn=rff_tile)
        shapes = ((p, N), (1, 1), (p + 1, M), (1, M), (1, 1), (1, N), (1, N))
    elif name == "bank_phi_gram":
        B = BANK[width]
        fn = functools.partial(bank_phi_gram_kernel, n_max=n,
                               interpret=False, tile_fn=phi_tile)
        shapes = ((B, p, N), (p, 3), (p * n, M), (B, 1, N), (B, 1, N))
    elif name == "hermite_phi":
        fn = functools.partial(hermite_phi_kernel, n_max=n, interpret=False)
        shapes = ((p, N), (p, 3), (p * n, M))
    elif name == "diag_quad":
        fn = functools.partial(diag_quad_kernel, interpret=False)
        shapes = ((N, M), (M, M))
    elif name == "scaled_gram":
        fn = functools.partial(scaled_gram_kernel, interpret=False)
        shapes = ((N, M), (1, M), (1, 1))
    else:
        raise ValueError(name)
    return fn, _args(sharding, *shapes)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("name", [
    "phi_gram_hermite", "phi_gram_rff", "bank_phi_gram", "hermite_phi",
    "diag_quad", "scaled_gram",
])
def test_kernel_compiles_for_v5e(one_chip, name, width):
    fn, args = _kernel_call(name, width, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
