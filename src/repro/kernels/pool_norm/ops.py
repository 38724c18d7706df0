"""Placement-dispatching jit wrapper for fused masked-pool + L2-normalize."""
from __future__ import annotations

import functools

import jax

from repro.kernels.placement import by_placement
from repro.kernels.pool_norm.pool_norm import pool_norm_pallas
from repro.kernels.pool_norm.ref import pool_norm_ref


@functools.partial(jax.jit, static_argnames=("pool", "backend", "block_b"))
def pool_norm(h, mask, pool: str = "mean", *, backend: str = "auto",
              block_b: int = 8):
    """h: (B, S, D); mask: (B, S) -> (B, D) float32 unit vectors.

    ``auto``: the compiled kernel where this call is placed on a TPU, the
    jnp reference elsewhere (``repro.kernels.placement``)."""
    kernel = functools.partial(pool_norm_pallas, pool=pool, block_b=block_b)
    reference = functools.partial(pool_norm_ref, pool=pool)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            reference, h, mask)
    if backend in ("pallas", "interpret"):
        return kernel(h, mask, interpret=backend == "interpret")
    return reference(h, mask)


__all__ = ["pool_norm", "pool_norm_pallas", "pool_norm_ref"]
