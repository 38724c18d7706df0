"""Placement-dispatching jit wrappers for flash attention.

* placed on a TPU    -> compiled Pallas kernel
* placed elsewhere   -> the pure-JAX paths of ``models.layers`` — same math
* tests              -> Pallas interpret mode vs ref.py oracle

``flash_attention_tokens`` takes the token-major (B, S, heads, hd) layout
the projections produce; ``flash_attention`` the head-major (B, H, S, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas, flash_attention_tokens_pallas)
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.placement import by_placement


def _tokens_jnp(q, k, v, *kv_len, causal: bool, window: int):
    from repro.models.layers import banded_attention_jnp, flash_attention_jnp

    Sq, Sk = q.shape[1], k.shape[1]
    kv_mask = None
    if kv_len:
        kv_mask = jnp.arange(Sk, dtype=jnp.int32)[None, :] < kv_len[0][:, None]
    if window and not causal:
        return banded_attention_jnp(q, k, v, window, kv_mask)
    return flash_attention_jnp(
        q, k, v, jnp.arange(Sq, dtype=jnp.int32),
        jnp.arange(Sk, dtype=jnp.int32), causal=causal, window=window,
        kv_mask=kv_mask)


def _tokens_kernel(q, k, v, *kv_len, interpret: bool, **kw):
    return flash_attention_tokens_pallas(
        q, k, v, interpret=interpret, kv_len=kv_len[0] if kv_len else None,
        **kw)


def block_sizes(window: int, causal: bool, itemsize: int) -> dict:
    """The kernel's tiles for a layer whose q, k, v elements take
    ``itemsize`` bytes: a bidirectional band is covered by three
    (128, 128) blocks per query block; global attention takes the widest
    tile that fits VMEM, where the grid's per-step cost is paid least
    often (on a v5e at (8, 8192, 16 x 64) in bf16: 71 TFLOP/s at
    (1024, 1024), 37 at (512, 512)).  Float32 operands (the int8 and
    fp32 serving paths) run out of VMEM at (1024, 1024)."""
    if window and not causal:
        return dict(block_q=128, block_k=128, block_h=8)
    tile = 1024 if itemsize <= 2 else 512
    return dict(block_q=tile, block_k=tile, block_h=1)


@functools.partial(jax.jit, static_argnames=("causal", "window", "backend"))
def flash_attention_tokens(q, k, v, *, causal: bool = True, window: int = 0,
                           backend: str = "auto", kv_len=None):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    ``kv_len`` (optional, (B,) int32): per-example valid-key prefix — the
    ragged-batch masking the bucketed embedder needs."""
    args = (q, k, v) + (() if kv_len is None else (kv_len,))
    kernel = functools.partial(_tokens_kernel, causal=causal, window=window,
                               **block_sizes(window, causal,
                                             q.dtype.itemsize))
    reference = functools.partial(_tokens_jnp, causal=causal, window=window)
    if backend == "auto":
        return by_placement(functools.partial(kernel, interpret=False),
                            reference, *args)
    if backend in ("pallas", "interpret"):
        return kernel(*args, interpret=backend == "interpret")
    return reference(*args)


@functools.partial(jax.jit, static_argnames=("causal", "window", "backend",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    backend: str = "auto", block_q: int = 128,
                    block_k: int = 128, kv_len=None):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd).

    ``kv_len`` (optional, (B,) int32): per-example valid-key prefix — the
    ragged-batch masking the bucketed embedder needs."""
    if backend in ("pallas", "interpret"):
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, block_q=block_q,
            block_k=block_k, interpret=backend == "interpret", kv_len=kv_len)
    t = functools.partial(jnp.moveaxis, source=1, destination=2)
    out = flash_attention_tokens(t(q), t(k), t(v), causal=causal,
                                 window=window, backend=backend,
                                 kv_len=kv_len)
    return jnp.moveaxis(out, 2, 1)


__all__ = ["flash_attention", "flash_attention_tokens",
           "flash_attention_pallas", "flash_attention_tokens_pallas",
           "attention_ref"]
