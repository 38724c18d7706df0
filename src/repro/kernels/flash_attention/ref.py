"""Pure-jnp oracle for flash attention: naive full-score attention.

Deliberately the SIMPLEST correct implementation (materialises the (Sq, Sk)
score matrix) — used only at test sizes.  The production pure-JAX path is
``repro.models.layers.flash_attention_jnp`` (chunked online softmax) and the
TPU path is the Pallas kernel; both are validated against this."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, window: int = 0,
                  sq_valid: int | None = None, sk_valid: int | None = None,
                  kv_len: jax.Array | None = None) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd).  GQA via H = KV * G.
    ``kv_len`` (optional, (B,)): per-example valid-key prefix length.
    Returns (B, H, Sq, hd) fp32-accurate attention output."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Sq, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qf, kf) / math.sqrt(hd)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    valid = jnp.ones((Sq, Sk), bool)
    if sq_valid is not None:
        valid &= qp < sq_valid
    if sk_valid is not None:
        valid &= kp < sk_valid
    if causal:
        valid &= kp <= qp
    if window:
        # causal: keys (i - window, i]; bidirectional: |i - j| <= window
        valid &= (kp > qp - window) if causal else (jnp.abs(kp - qp) <= window)
    s = jnp.where(valid[None, None, None], s, -jnp.inf)
    if kv_len is not None:
        kvalid = jnp.arange(Sk)[None, :] < kv_len[:, None]          # (B, Sk)
        s = jnp.where(kvalid[:, None, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    out = jnp.einsum("bkgqs,bksh->bkgqh", w, vf)
    return out.reshape(B, H, Sq, hd).astype(q.dtype)
