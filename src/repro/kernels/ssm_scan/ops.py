"""Placement-dispatching jit wrapper for the selective scan."""
from __future__ import annotations

import functools

import jax

from repro.kernels.placement import by_placement
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.ssm_scan.ssm_scan import ssm_scan_pallas


@functools.partial(jax.jit, static_argnames=("backend", "chunk", "block_di"))
def ssm_scan(x, dt, Bm, Cm, A, *, backend: str = "auto", chunk: int = 128,
             block_di: int = 512):
    kernel = functools.partial(ssm_scan_pallas, chunk=chunk,
                               block_di=block_di)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            ssm_scan_ref, x, dt, Bm, Cm, A)
    if backend in ("pallas", "interpret"):
        return kernel(x, dt, Bm, Cm, A, interpret=backend == "interpret")
    return ssm_scan_ref(x, dt, Bm, Cm, A)


__all__ = ["ssm_scan", "ssm_scan_pallas", "ssm_scan_ref"]
