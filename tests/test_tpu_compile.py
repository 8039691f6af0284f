"""Compile every Pallas kernel of the main path for a described TPU v5e.

Interpret mode (the CPU tests) cannot see what the chip's compiler
refuses: a block not aligned to the (8, 128) tiling, more VMEM than a
kernel may use, a lowering Mosaic does not implement.  These tests hand
the raw kernels (``interpret=False``; the ``ops`` wrappers would pick
interpret mode from the CPU backend) to the TPU compiler for one chip of
a described ``v5e:2x2`` topology, at a small width and at the paper's
width (p=4, n=11: M=14641 padded to 14848, N=10240), and check that the
compiled program holds the kernel.  The fit's two phases are compiled
through ``ops.fused_fit_moments`` with ``interpret=False``: at the small
width its rows run in eight chunks (the accumulating Gram under a scan),
at the paper's width in one.  Nothing runs; no chip is needed.

The serving solve is pinned beside them: with the fit's prepared factor
``_posterior_mean_var`` stays one jit of that name (the benchmark's
trace readers match ``jit__posterior_mean_var``) and neither inverts L's
diagonal blocks nor passes over the whole M x M factor per call.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fagp
from repro.kernels import ops
from repro.kernels.diag_quad import diag_quad_kernel
from repro.kernels.gram import gram_moments_kernel
from repro.kernels.hermite_phi import hermite_phi_kernel, phi_tile
from repro.kernels.phi_gram import bank_phi_gram_kernel
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
    if name in ("fused_fit_hermite", "fused_fit_rff"):
        tile = phi_tile if name == "fused_fit_hermite" else rff_tile
        fn = functools.partial(ops.fused_fit_moments, n_max=n,
                               interpret=False, tile_fn=tile)
        table = (p * n, M) if tile is phi_tile else (p + 1, M)
        consts = (p, 3) if tile is phi_tile else (1, 1)
        shapes = ((N, p), (N,), consts, table, (M,), ())
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
        fn = functools.partial(gram_moments_kernel, interpret=False)
        shapes = ((N, M), (1, N), (1, N), (1, M), (1, 1))
    elif name == "gram_moments_chunk":
        # a middle row chunk of the fit: raw moments added in place
        block_m, block_k, _, rows = ops.fit_chunks(N, M)
        fn = functools.partial(gram_moments_kernel, block_m=block_m,
                               block_k=block_k, scale=False,
                               interpret=False)
        args = _args(sharding, (rows, M), (1, rows), (1, rows), (1, M),
                     (1, 1))
        return (lambda *a: fn(*a[:5], acc=a[5:]),
                args + _args(sharding, (M, M), (1, M)))
    else:
        raise ValueError(name)
    return fn, _args(sharding, *shapes)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("name", [
    "fused_fit_hermite", "fused_fit_rff", "bank_phi_gram", "hermite_phi",
    "diag_quad", "scaled_gram", "gram_moments_chunk",
])
def test_kernel_compiles_for_v5e(one_chip, name, width):
    fn, args = _kernel_call(name, width, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_fused_fit_kernels_carry_its_name(one_chip, width):
    """Both phases' Pallas calls, in every row chunk, are named after
    ``fused_fit_moments``: the trace reader of the fit's roofline
    (bench/layers/phi_gram_roofline.py) finds its ops by that name."""
    p, n, _, N = WIDTHS[width]
    M = n ** p
    fn = functools.partial(ops.fused_fit_moments, n_max=n, interpret=False)
    args = _args(one_chip, (N, p), (N,), (p, 3), (p * n, M), (M,), ())
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = re.findall(r"^\s*(?:ROOT )?(%?[\w.-]+) = .*custom_call_target=\"tpu_custom_call\"",
                       text, re.M)
    assert len(names) >= 2, names
    assert all(re.match(r"%?fused_fit_moments\.\d+$", x) for x in names), names


def _serving_args(M, Q, prepared, sharding=None):
    """Argument shapes of ``_posterior_mean_var`` for an M-feature model
    and Q query rows, with the fit's prepared blocks or without."""
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    tb = fagp._solve_block(M)
    dinv = f32(-(-M // tb), tb, tb) if prepared else None
    return f32(M), (f32(M, M), dinv), f32(M), f32(Q, M)


def _primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _primitives(sub)


@pytest.mark.parametrize("prepared", [True, False])
def test_serving_solve_is_one_jit_and_reinverts_only_unprepared(prepared):
    args = _serving_args(1000, 64, prepared)
    closed = jax.make_jaxpr(lambda *a: fagp._posterior_mean_var(*a))(*args)
    (call,) = closed.jaxpr.eqns
    assert call.params["name"] == "_posterior_mean_var"
    names = {e.primitive.name for e in _primitives(closed.jaxpr)}
    assert ("triangular_solve" in names) == (not prepared), names


def test_prepared_serving_solve_compiles_for_v5e(one_chip):
    """Compiled for the chip at a ragged M (TB = 512 and a last block of
    45): no ``InvertDiagBlocksLowerTriangular`` custom call and no op whose
    result is the whole M x M factor."""
    M = 4141
    args = _serving_args(M, 256, True, one_chip)
    text = fagp._posterior_mean_var.lower(*args).compile().as_text()
    assert "InvertDiagBlocks" not in text
    whole = [ln for ln in text.splitlines()
             if re.search(rf"= f32\[{M},{M}\]\S* (?!parameter\()", ln)]
    assert not whole, whole[:3]
