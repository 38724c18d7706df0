"""Placement-dispatching jit wrapper for fused RMSNorm."""
from __future__ import annotations

import functools

import jax

from repro.kernels.placement import by_placement
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("eps", "backend", "block_rows"))
def rmsnorm(x, scale, eps: float = 1e-5, *, backend: str = "auto",
            block_rows: int = 256):
    kernel = functools.partial(rmsnorm_pallas, eps=eps, block_rows=block_rows)
    reference = functools.partial(rmsnorm_ref, eps=eps)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            reference, x, scale)
    if backend in ("pallas", "interpret"):
        return kernel(x, scale, interpret=backend == "interpret")
    return reference(x, scale)


__all__ = ["rmsnorm", "rmsnorm_pallas", "rmsnorm_ref"]
