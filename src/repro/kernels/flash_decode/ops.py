"""Placement-dispatching jit wrapper for flash-decode attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.kernels.flash_decode.ref import decode_attention_ref
from repro.kernels.placement import by_placement


@functools.partial(jax.jit, static_argnames=("window", "backend", "block_k"))
def flash_decode(q, k, v, kpos, pos, *, window: int = 0,
                 backend: str = "auto", block_k: int = 256):
    kernel = functools.partial(flash_decode_pallas, window=window,
                               block_k=block_k)
    reference = functools.partial(decode_attention_ref, window=window)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            reference, q, k, v, kpos, pos)
    if backend in ("pallas", "interpret"):
        return kernel(q, k, v, kpos, pos, interpret=backend == "interpret")
    return reference(q, k, v, kpos, pos)


__all__ = ["flash_decode", "flash_decode_pallas", "decode_attention_ref"]
