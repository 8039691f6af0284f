"""Shared shard-local spec plumbing for every shard_map schedule.

Both the v2 row-sharding fit/predict (``core.distributed``) and the
bank-axis sharding (``bank.sharded``) rebuild a :class:`~repro.core.fagp.GPSpec`
from shard-local leaves inside a ``shard_map`` body, probe mesh sizes, and
thread the optional spectral-draw leaf as a ``*args`` tail.  This module is
the single home for that glue — a third copy-paste was the alternative.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from .fagp import GPSpec

__all__ = ["spec_local", "omega_args", "mesh_size", "axis_size"]


def spec_local(spec: GPSpec, eps, rho, omega) -> GPSpec:
    """Rebuild the spec from shard-local leaves inside a shard_map body —
    every data leaf is replaced, so no outer traced value leaks into the
    body through the closure."""
    return dataclasses.replace(
        spec, eps=eps, rho=rho, noise=jnp.asarray(0.0, jnp.float32),
        omega=omega,
    )


def omega_args(spec: GPSpec) -> tuple:
    """The spec's optional spectral-draw leaf as a *args tail (present only
    when the expansion carries one — keeps the hermite schedules byte-
    identical to before)."""
    return () if spec.omega is None else (spec.omega,)


def mesh_size(mesh) -> int:
    """Total chip count of a mesh (product over every axis)."""
    return int(np.prod(list(mesh.shape.values())))


def axis_size(mesh, axis: str, default: int = 1) -> int:
    """Size of one named mesh axis (``default`` when the axis is absent)."""
    return int(mesh.shape.get(axis, default))
