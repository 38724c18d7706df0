"""Flash attention Pallas TPU kernel: blockwise online softmax.

TPU adaptation of the attention hot-spot (DESIGN.md §6):
* grid = (B, H, num_q_blocks, num_kv_blocks); the kv dim is the innermost
  (sequential) axis so the (block_q, hd) accumulator, running max and
  denominator live in VMEM scratch across kv steps — score blocks NEVER
  touch HBM (the pure-JAX path materialises them; see §Roofline notes).
* BlockSpecs tile q/o as (1, 1, block_q, head_dim) and k/v as
  (1, 1, block_k, head_dim) — MXU-aligned when block_* are multiples of 128
  and head_dim is 64/128.
* GQA is expressed in the k/v index_map (kv_head = head // group_size), so
  grouped queries reuse the same k/v VMEM tile with no gather.
* causal / sliding-window masks come from program-id iota — no mask tensor;
  the per-example valid-key count ``kv_len`` is scalar-prefetched to SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.placement import dot_precision

NEG_INF = -1e30


def _flash_kernel(kvl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, d_ref,
                  *, scale: float, causal: bool, window: int,
                  sq: int, block_q: int, block_k: int, nk: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    q = q_ref[0, 0]                                      # (bq, hd)
    k = k_ref[0, 0]                                      # (bk, hd)
    v = v_ref[0, 0]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        precision=dot_precision(q.dtype),
                        preferred_element_type=jnp.float32) * scale

    qp = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kp = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # per-example valid-key prefix (ragged batches: bucketed embedder pads
    # each row to the bucket; padded keys must not enter the softmax)
    valid = (qp < sq) & (kp < kvl_ref[b])
    if causal:
        valid &= kp <= qp
    if window:
        valid &= kp > qp - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    d_ref[...] = d_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jnp.dot(p.astype(v.dtype), v,
                              precision=dot_precision(v.dtype),
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(d_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True,
                           kv_len: jax.Array | None = None) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd).

    ``kv_len`` (optional, (B,) int32): per-example count of valid keys —
    keys at positions >= kv_len[b] are masked out (ragged/bucketed batches
    where each row is left-aligned and padded to the bucket).  Defaults to
    all Sk keys valid.  ``interpret=True`` runs the kernel body under the
    Pallas interpreter (any platform); ``interpret=False`` is the compiled
    TPU kernel."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0, "num_heads must be a multiple of num_kv_heads"
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    pq, pk = nq * bq - Sq, nk * bk - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if kv_len is None:
        kv_len = jnp.full((B,), Sk, jnp.int32)
    # one valid-key bound per batch row, scalar-prefetched into SMEM
    kvl = jnp.minimum(kv_len.astype(jnp.int32), Sk)

    # the per-example kvl bound (clamped to the unpadded Sk) also masks the
    # block-padding key tail, so no separate `kp < Sk` guard is needed
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, sq=Sq, block_q=bq, block_k=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, hd),
                             lambda b, h, qi, ki, kvl: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, qi, ki, kvl: (b, h // G, ki, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, qi, ki, kvl: (b, h // G, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, hd),
                                   lambda b, h, qi, ki, kvl: (b, h, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
                pltpu.VMEM((bq, 1), jnp.float32),    # running max
                pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(kvl, q, k, v)
    return out[:, :, :Sq]
