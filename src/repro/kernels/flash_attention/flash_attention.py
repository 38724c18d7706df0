"""Flash attention Pallas TPU kernel: blockwise online softmax.

TPU adaptation of the attention hot-spot (DESIGN.md §6):
* token-major operands: q, k, v and the output are (B, S, heads x hd), as
  the projections produce them, so no transpose runs around the kernel.
  A grid step holds the lanes of ``block_h`` x 128 of that last dim; with
  heads narrower than 128 lanes (64: two per group) each head's scores
  come from its own lanes alone (the others are zeroed in the query), and
  its weighted values are kept in its own lanes of the accumulator.
* grid = (B, lane blocks, q blocks, kv blocks); the kv dim is innermost
  (sequential), so the (block_q, lanes) accumulator and each head's
  running max and denominator live in VMEM scratch across kv steps —
  score blocks NEVER touch HBM.
* a window or causality bounds each query block's keys to a band of kv
  blocks: the kv grid walks that band only (its length is the widest
  band), blocks past a row's valid keys (``kv_len``) or past the band are
  neither fetched again nor computed, so a banded layer costs work in
  proportion to S x window, not S^2.
* masks come from program-id iota — no mask tensor — and only a block
  that crosses the band's edge or the valid-key bound applies one; the
  per-example valid-key count ``kv_len`` is scalar-prefetched to SMEM.
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
LANES = 128


def _band(qi, *, causal: bool, window: int, block_q: int, block_k: int,
          nk: int, mx=jnp.maximum, mn=jnp.minimum):
    """(first, last) kv block that queries of q block ``qi`` may attend:
    a causal query attends keys (i - window, i] (all of [0, i] without a
    window), a bidirectional one |i - j| <= window (all keys without).
    ``mx``/``mn``: the builtins for a Python ``qi``."""
    q0 = qi * block_q
    q1 = q0 + block_q - 1
    if causal:
        lo = (mx(q0 - window + 1, 0) // block_k) if window else 0
        hi = mn(q1 // block_k, nk - 1)
    elif window:
        lo = mx(q0 - window, 0) // block_k
        hi = mn((q1 + window) // block_k, nk - 1)
    else:
        lo, hi = 0, nk - 1
    return lo, hi


def _flash_kernel(kvl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, window: int, hd: int,
                  group: int, groups: int, block_q: int, block_k: int,
                  nk: int, nkb: int):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    per = group // hd                       # heads in one lane group
    band = functools.partial(_band, causal=causal, window=window,
                             block_q=block_q, block_k=block_k, nk=nk)
    lo, hi = band(qi)
    kb = lo + ki
    kvl = kvl_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0, k0 = qi * block_q, kb * block_k
    q1, k1 = q0 + block_q - 1, k0 + block_k - 1
    live = (kb <= hi) & (k0 < kvl)
    whole = k1 < kvl                        # no key of the block is masked
    if causal:
        whole &= k1 <= q0
        if window:
            whole &= k0 > q1 - window
    elif window:
        whole &= (k1 - q0 <= window) & (q1 - k0 <= window)
    lane_head = lax.broadcasted_iota(jnp.int32, (1, group), 1) // hd

    def step(masked: bool):
        if masked:
            qp = q0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kp = k0 + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            valid = kp < kvl
            if causal:
                valid &= kp <= qp
                if window:
                    valid &= kp > qp - window
            elif window:
                valid &= jnp.abs(kp - qp) <= window
        for g in range(groups):
            lanes = pl.ds(g * group, group)
            q = (q_ref[0, :, lanes] * scale).astype(q_ref.dtype)
            k = k_ref[0, :, lanes]
            v = v_ref[0, :, lanes]
            pv_all = corr_all = None
            for j in range(per):
                h = g * per + j
                qj = q if per == 1 else jnp.where(lane_head == j, q, 0)
                s = lax.dot_general(qj, k, (((1,), (1,)), ((), ())),
                                    precision=dot_precision(q.dtype),
                                    preferred_element_type=jnp.float32)
                if masked:
                    s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[h]                            # (bq, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
                m_ref[h] = m_new
                pv = jnp.dot(p.astype(v.dtype), v,
                             precision=dot_precision(v.dtype),
                             preferred_element_type=jnp.float32)
                if j == 0:
                    pv_all = pv
                    corr_all = jnp.broadcast_to(corr, pv.shape)
                else:
                    mine = lane_head == j
                    pv_all = jnp.where(mine, pv, pv_all)
                    corr_all = jnp.where(mine, corr, corr_all)
            acc_ref[:, lanes] = acc_ref[:, lanes] * corr_all + pv_all

    @pl.when(live & whole)
    def _whole():
        step(masked=False)

    @pl.when(live & jnp.logical_not(whole))
    def _edge():
        step(masked=True)

    @pl.when(ki == nkb - 1)
    def _finalize():
        for g in range(groups):
            lanes = pl.ds(g * group, group)
            den = None
            for j in range(per):
                d = l_ref[g * per + j]
                den = (jnp.broadcast_to(d, (block_q, group)) if j == 0
                       else jnp.where(lane_head == j, d, den))
            o_ref[0, :, lanes] = (acc_ref[:, lanes] / jnp.maximum(den, 1e-30)
                                  ).astype(o_ref.dtype)


def flash_attention_tokens_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                                  *, causal: bool = True, window: int = 0,
                                  block_q: int = 128, block_k: int = 128,
                                  block_h: int = 1, interpret: bool = True,
                                  kv_len: jax.Array | None = None
                                  ) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    ``window``: a causal query attends keys (i - window, i], a
    bidirectional one keys with |i - j| <= window; 0 is no window.
    ``kv_len`` (optional, (B,) int32): per-example count of valid keys —
    keys at positions >= kv_len[b] are masked out (ragged/bucketed batches
    where each row is left-aligned and padded to the bucket).  Defaults to
    all Sk keys valid.  ``block_h``: 128-lane groups of heads per grid
    step.  ``interpret=True`` runs the kernel body under the Pallas
    interpreter (any platform); ``interpret=False`` is the compiled TPU
    kernel."""
    B, Sq, H, hd = q.shape
    KV, Sk = k.shape[2], k.shape[1]
    assert H % KV == 0, "num_heads must be a multiple of num_kv_heads"
    if KV != H:                 # grouped heads: each query head its own kv
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    width = H * hd
    group = min(LANES, width)
    assert group % hd == 0 and width % group == 0, (H, hd)
    groups = min(block_h, width // group)
    while (width // group) % groups:
        groups -= 1
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    q = jnp.pad(q.reshape(B, Sq, width), ((0, 0), (0, nq * bq - Sq), (0, 0)))
    k, v = (jnp.pad(t.reshape(B, Sk, width), ((0, 0), (0, nk * bk - Sk),
                                              (0, 0))) for t in (k, v))
    if kv_len is None:
        kv_len = jnp.full((B,), Sk, jnp.int32)
    # one valid-key bound per batch row, scalar-prefetched into SMEM; it
    # also masks the block-padding key tail
    kvl = jnp.minimum(kv_len.astype(jnp.int32), Sk)
    band = functools.partial(_band, causal=causal, window=window,
                             block_q=bq, block_k=bk, nk=nk)
    # the kv grid is as long as the widest band of any q block
    nkb = max(hi - lo + 1 for lo, hi in (band(i, mx=max, mn=min)
                                         for i in range(nq)))

    def kv_block(b, h, qi, ki, kvl):
        lo, hi = band(qi)
        # past the band or the valid keys: the block already fetched
        last = jnp.maximum(kvl[b] - 1, 0) // bk
        return (b, jnp.minimum(jnp.minimum(lo + ki, hi), last), h)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, hd=hd, group=group, groups=groups, block_q=bq,
        block_k=bk, nk=nk, nkb=nkb)
    lanes = group * groups
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, width // lanes, nq, nkb),
            in_specs=[
                pl.BlockSpec((1, bq, lanes),
                             lambda b, h, qi, ki, kvl: (b, qi, h)),
                pl.BlockSpec((1, bk, lanes), kv_block),
                pl.BlockSpec((1, bk, lanes), kv_block),
            ],
            out_specs=pl.BlockSpec((1, bq, lanes),
                                   lambda b, h, qi, ki, kvl: (b, qi, h)),
            scratch_shapes=[
                pltpu.VMEM((bq, lanes), jnp.float32),         # accumulator
                pltpu.VMEM((groups * (group // hd), bq, 1),
                           jnp.float32),                       # running max
                pltpu.VMEM((groups * (group // hd), bq, 1),
                           jnp.float32),                       # denominator
            ]),
        out_shape=jax.ShapeDtypeStruct((B, nq * bq, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(kvl, q, k, v)
    return out[:, :Sq].reshape(B, Sq, H, hd)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True,
                           kv_len: jax.Array | None = None) -> jax.Array:
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd): the
    head-major layout of ``flash_attention_tokens_pallas``."""
    out = flash_attention_tokens_pallas(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret, kv_len=kv_len)
    return jnp.moveaxis(out, 2, 1)
