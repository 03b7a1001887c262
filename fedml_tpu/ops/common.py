"""Shared helpers for the Pallas TPU kernels in this package."""

from __future__ import annotations

import jax


def sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-manual-axes of ``like`` — under
    shard_map (the cross-silo mesh round) pallas outputs must declare how
    they vary across the mesh; outside shard_map vma is empty and harmless."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def interpret() -> bool:
    """Pallas TPU kernels run in interpret mode on CPU backends (unit
    tests / virtual meshes); compiled on real TPUs."""
    return jax.default_backend() != "tpu"
