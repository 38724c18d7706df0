"""Placement-dispatching jit wrapper for flash attention.

* placed on a TPU    -> compiled Pallas kernel
* placed elsewhere   -> chunked pure-JAX flash (models.layers) — same math
* tests              -> Pallas interpret mode vs ref.py oracle
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.placement import by_placement


def _flash_attention_jnp(q, k, v, *kv_len, causal: bool, window: int):
    from repro.models.layers import flash_attention_jnp

    Sq, Sk = q.shape[2], k.shape[2]
    kv_mask = None
    if kv_len:
        kv_mask = jnp.arange(Sk, dtype=jnp.int32)[None, :] < kv_len[0][:, None]
    out = flash_attention_jnp(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        jnp.arange(Sq, dtype=jnp.int32), jnp.arange(Sk, dtype=jnp.int32),
        causal=causal, window=window, kv_mask=kv_mask)
    return jnp.moveaxis(out, 2, 1)


def _flash_attention_kernel(q, k, v, *kv_len, interpret: bool, **kw):
    return flash_attention_pallas(q, k, v, interpret=interpret,
                                  kv_len=kv_len[0] if kv_len else None, **kw)


@functools.partial(jax.jit, static_argnames=("causal", "window", "backend",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    backend: str = "auto", block_q: int = 128,
                    block_k: int = 128, kv_len=None):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd).

    ``kv_len`` (optional, (B,) int32): per-example valid-key prefix — the
    ragged-batch masking the bucketed embedder needs."""
    args = (q, k, v) + (() if kv_len is None else (kv_len,))
    kernel = functools.partial(_flash_attention_kernel, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k)
    reference = functools.partial(_flash_attention_jnp, causal=causal,
                                  window=window)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            reference, *args)
    if backend in ("pallas", "interpret"):
        return kernel(*args, interpret=backend == "interpret")
    return reference(*args)


__all__ = ["flash_attention", "flash_attention_pallas", "attention_ref"]
