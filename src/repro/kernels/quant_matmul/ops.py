"""Placement-dispatching jit wrappers for the fused int8 quant matmuls.

``_quant_matmul`` / ``_quant_matmul_w8a8`` are the unjitted impls (exposed
so dispatch tests can lower them for a chosen platform without fighting jit
caches); ``quant_matmul`` / ``quant_matmul_w8a8`` are the jitted entries
every serving call site uses.  ``auto`` runs the compiled Pallas kernel
where the call is placed on a TPU and the jnp oracle elsewhere
(``repro.kernels.placement``).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.placement import by_placement
from repro.kernels.quant_matmul import quant_matmul as _kmod
from repro.kernels.quant_matmul import ref as _rmod
from repro.kernels.quant_matmul.quant_matmul import (quantize_activations,
                                                    quant_matmul_pallas,
                                                    w8a8_matmul_pallas)
from repro.kernels.quant_matmul.ref import quant_matmul_ref, w8a8_matmul_ref


def _quant_matmul(x, w8, scale, *, backend: str = "auto", block_m: int = 128,
                  block_n: int = 128, block_k: int = 128):
    kernel = functools.partial(_kmod.quant_matmul_pallas, block_m=block_m,
                               block_n=block_n, block_k=block_k)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            _rmod.quant_matmul_ref, x, w8, scale)
    if backend in ("pallas", "interpret"):
        return kernel(x, w8, scale, interpret=backend == "interpret")
    return _rmod.quant_matmul_ref(x, w8, scale)


@functools.partial(jax.jit, static_argnames=("backend", "block_m", "block_n",
                                             "block_k"))
def quant_matmul(x, w8, scale, *, backend: str = "auto", block_m: int = 128,
                 block_n: int = 128, block_k: int = 128):
    """x: (..., K) float; w8: (K, N) int8; scale: (N,) fp32 -> (..., N).

    Weight-only route: float activations, fp32 accumulation, dequant-by-
    weight-scale epilogue (W8A16/W8A32 depending on the activation dtype).
    """
    return _quant_matmul(x, w8, scale, backend=backend, block_m=block_m,
                         block_n=block_n, block_k=block_k)


def _quant_matmul_w8a8(x, w8, w_scale, *, backend: str = "auto",
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 128):
    x8, x_scale = quantize_activations(x)
    kernel = functools.partial(_kmod.w8a8_matmul_pallas, block_m=block_m,
                               block_n=block_n, block_k=block_k,
                               out_dtype=x.dtype)
    reference = functools.partial(_rmod.w8a8_matmul_ref, out_dtype=x.dtype)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            reference, x8, w8, x_scale, w_scale)
    if backend in ("pallas", "interpret"):
        return kernel(x8, w8, x_scale, w_scale,
                      interpret=backend == "interpret")
    return reference(x8, w8, x_scale, w_scale)


@functools.partial(jax.jit, static_argnames=("backend", "block_m", "block_n",
                                             "block_k"))
def quant_matmul_w8a8(x, w8, w_scale, *, backend: str = "auto",
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128):
    """x: (..., K) float; w8: (K, N) int8; w_scale: (N,) fp32 -> (..., N).

    W8A8 route: quantizes the activations on the fly (per-row dynamic
    symmetric absmax — fused into the same jit so the int8 activations are
    produced right where the kernel consumes them), contracts int8 x int8
    with int32 accumulation, and dequantizes once in the epilogue by
    ``act_scale[:, None] * w_scale[None, :]``.
    """
    return _quant_matmul_w8a8(x, w8, w_scale, backend=backend,
                              block_m=block_m, block_n=block_n,
                              block_k=block_k)


__all__ = ["quant_matmul", "quant_matmul_w8a8", "quant_matmul_pallas",
           "w8a8_matmul_pallas", "quant_matmul_ref", "w8a8_matmul_ref",
           "quantize_activations"]
