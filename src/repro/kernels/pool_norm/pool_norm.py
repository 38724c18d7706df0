"""Fused masked-pool + L2-normalize Pallas TPU kernel.

The embedder's serving epilogue: mask-weighted pooling over the sequence
axis and L2 normalisation of the pooled vector, in ONE pass over the
(B, S, D) hidden states.  Unfused XLA lowers this tail as separate
multiply / reduce / norm / divide HBM round-trips over the hidden-state
tensor; fused it is one read of the hiddens + one (B, D) write.  Pooling
and the norm both accumulate in fp32 regardless of the compute dtype (the
paper serves fp32 embedding vectors).

The grid is (batch blocks, sequence blocks).  A sequence block is the whole
sequence when its (block_b, S, D) tile fits ``VMEM_BUDGET``, else the
largest multiple of 128 tokens that does (128 is the lane width of the mask
tile), so fp32 hiddens at S=512 fit the chip's scoped VMEM.  The pooled sum
and the token count accumulate in VMEM scratch across sequence blocks.
``cls`` pooling reads only the first sequence tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes one double-buffered hidden-state tile may take; the fp32
# temporaries of the tile and the small mask/output/scratch tiles take the
# rest of VMEM_LIMIT
VMEM_BUDGET = 8 * 2 ** 20
VMEM_LIMIT = 32 * 2 ** 20
# cls reads token 0 only: one (16, D) tile covers the sublane tiling of
# every dtype up to bf16
CLS_TOKENS = 16


def seq_block(bb: int, S: int, D: int, itemsize: int) -> int:
    """Tokens per sequence block for a (bb, S, D) tile of ``itemsize``."""
    fit = VMEM_BUDGET // (2 * bb * D * itemsize)
    if fit >= S:
        return S
    return max(128, fit // 128 * 128)


def _pool_norm_kernel(h_ref, m_ref, o_ref, acc_ref, cnt_ref, *, pool: str,
                      ns: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    h = h_ref[...].astype(jnp.float32)           # (bb, bs, D)
    m = m_ref[...].astype(jnp.float32)           # (bb, bs)
    if pool == "mean":
        acc_ref[...] += (h * m[..., None]).sum(1)
        cnt_ref[...] += m.sum(1, keepdims=True)
    else:  # cls — zeroed for fully-masked (padding) rows, like the ref
        acc_ref[...] += h[:, 0] * jnp.minimum(m[:, :1], 1.0)
        cnt_ref[...] = jnp.ones_like(cnt_ref)

    @pl.when(s == ns - 1)
    def _finalize():
        pooled = acc_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        nrm = jnp.sqrt(jnp.sum(pooled * pooled, axis=-1, keepdims=True))
        o_ref[...] = pooled / jnp.maximum(nrm, 1e-9)


def pool_norm_pallas(h: jax.Array, mask: jax.Array, pool: str = "mean", *,
                     block_b: int = 8, interpret: bool = True) -> jax.Array:
    """h: (B, S, D); mask: (B, S) -> (B, D) float32, L2-normalised."""
    if pool not in ("mean", "cls"):
        raise ValueError(f"unknown pool mode {pool!r}")
    B, S, D = h.shape
    bb = min(block_b, B)
    nb = -(-B // bb)
    if pool == "cls":
        bs = min(S, CLS_TOKENS)
        h, mask, ns = h[:, :bs], mask[:, :bs], 1
    else:
        bs = seq_block(bb, S, D, h.dtype.itemsize)
        ns = -(-S // bs)
    pad_b, pad_s = nb * bb - B, ns * bs - h.shape[1]
    if pad_b or pad_s:
        # padding rows and tokens carry an all-zero mask -> they add nothing
        # to the pooled sum (a padding row pools to the zero vector)
        h = jnp.pad(h, ((0, pad_b), (0, pad_s), (0, 0)))
        mask = jnp.pad(mask, ((0, pad_b), (0, pad_s)))
    out = pl.pallas_call(
        functools.partial(_pool_norm_kernel, pool=pool, ns=ns),
        grid=(nb, ns),
        in_specs=[
            pl.BlockSpec((bb, bs, D), lambda i, s: (i, s, 0)),
            pl.BlockSpec((bb, bs), lambda i, s: (i, s)),
        ],
        out_specs=pl.BlockSpec((bb, D), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, D), jnp.float32),
                        pltpu.VMEM((bb, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(h, mask)
    return out[:B]
