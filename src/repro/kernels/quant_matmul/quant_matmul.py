"""Fused int8 quant matmul Pallas TPU kernels (W8A16/W8A32 and W8A8).

``quant_matmul_pallas`` is the weight-only variant: each (block_m, block_k)
float activation tile contracts against a (block_k, block_n) **int8** weight
tile straight out of VMEM — the weights travel HBM->VMEM at 1 byte/element
(4x less traffic than fp32-resident serving, 2x less than bf16) and are
widened to the activation dtype only inside the tile, in registers.
Accumulation is fp32 across the K grid axis in a VMEM scratch; the
per-output-channel dequant scale is applied ONCE in the epilogue on the
final K step, so a dequantized weight matrix never exists in any memory
space.

``w8a8_matmul_pallas`` goes the rest of the way: int8 activations (produced
by ``quantize_activations``' per-row dynamic symmetric scheme) contract
against the int8 weights with **int32** accumulation
(``preferred_element_type=jnp.int32``) in a VMEM scratch — no int8->float
widening inside the tile, so the contraction is eligible for the MXU's int8
rate and the activation side of HBM traffic shrinks 4x too.  Dequant happens
once in the epilogue as ``act_scale[:, None] * w_scale[None, :]``.

Tiling note (guide §Tiling Constraints): int8 VMEM tiles want (32, 128)
sublane x lane minima, so the defaults keep ``block_k`` / ``block_n`` at
128 multiples; ragged M/K/N are zero-padded to the block grid (zero rows
contract to zero — exactly, in int32 — and the padded output is sliced
off).  Padded scale lanes are 1.0 so the epilogue multiply stays finite.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.placement import dot_precision


def quantize_activations(x: jax.Array):
    """Per-row dynamic symmetric int8 quantization of ``x: (..., K)``.

    Returns ``(x8, scale)`` with ``x8`` int8 of x's shape and ``scale``
    fp32 of shape ``x.shape[:-1]`` such that ``x8 * scale[..., None] ~= x``.
    The scale divide is guarded twice: all-zero rows get scale 1.0 (their
    quantized row is exactly zero), and subnormal absmax rows clamp the
    scale to the smallest normal fp32 so ``x / scale`` can never overflow
    past the [-127, 127] clip (|x| <= absmax < 127 * tiny => |x/scale| < 127).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    tiny = jnp.float32(jnp.finfo(jnp.float32).tiny)
    scale = jnp.maximum(amax / 127.0, tiny)
    scale = jnp.where(amax > 0, scale, 1.0)
    x8 = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return x8, scale


def _quant_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # (bm, bk) activations
    w = w_ref[...].astype(x.dtype)                   # (bk, bn) int8 widened
    acc_ref[...] += jnp.dot(x, w, precision=dot_precision(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        scale = s_ref[...].astype(jnp.float32)       # (1, bn) per out channel
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


def quant_matmul_pallas(x: jax.Array, w8: jax.Array, scale: jax.Array, *,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 128, out_dtype=None,
                        interpret: bool = False) -> jax.Array:
    """x: (..., K) float; w8: (K, N) int8; scale: (N,) -> (..., N).

    Compiled unless ``interpret=True`` asks for the Pallas interpreter.
    """
    if w8.dtype != jnp.int8:
        raise TypeError(f"quantized weights must be int8, got {w8.dtype}")
    *lead, K = x.shape
    N = w8.shape[1]
    out_dtype = x.dtype if out_dtype is None else out_dtype
    xf = x.reshape(-1, K)
    M = xf.shape[0]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    nm, nn, nk = -(-M // bm), -(-N // bn), -(-K // bk)
    pm, pn, pk = nm * bm - M, nn * bn - N, nk * bk - K
    if pm or pk:
        xf = jnp.pad(xf, ((0, pm), (0, pk)))
    if pk or pn:
        w8 = jnp.pad(w8, ((0, pk), (0, pn)))
    # scales ride as 2-D (1, N) rows: Mosaic tiles 1-D operands differently
    # from XLA's layout and refuses them
    scale = scale.reshape(1, N)
    if pn:
        scale = jnp.pad(scale, ((0, 0), (0, pn)), constant_values=1.0)
    out = pl.pallas_call(
        functools.partial(_quant_matmul_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xf, w8, scale)
    return out[:M, :N].reshape(*lead, N)


def _w8a8_matmul_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                        nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32: both operands stay int8 into the dot so the
    # contraction is MXU-int8-eligible; the accumulator is exact.
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        xs = xs_ref[...].astype(jnp.float32)         # (bm, 1) per act row
        ws = ws_ref[...].astype(jnp.float32)         # (1, bn) per out channel
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * xs * ws).astype(o_ref.dtype)


def w8a8_matmul_pallas(x8: jax.Array, w8: jax.Array, x_scale: jax.Array,
                       w_scale: jax.Array, *, block_m: int = 128,
                       block_n: int = 128, block_k: int = 128,
                       out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """x8: (..., K) int8; w8: (K, N) int8; x_scale: x8.shape[:-1];
    w_scale: (N,) -> (..., N) float.

    Accumulates int32 in VMEM scratch across the K grid axis and dequantizes
    once in the epilogue by ``x_scale[:, None] * w_scale[None, :]`` — neither
    operand is ever widened to float inside the tile.  Compiled unless
    ``interpret=True``.
    """
    if x8.dtype != jnp.int8:
        raise TypeError(f"quantized activations must be int8, got {x8.dtype}")
    if w8.dtype != jnp.int8:
        raise TypeError(f"quantized weights must be int8, got {w8.dtype}")
    *lead, K = x8.shape
    N = w8.shape[1]
    xq = x8.reshape(-1, K)
    # both scale vectors ride 2-D, (M, 1) and (1, N), like the weight-only
    # kernel's: Mosaic refuses 1-D operands
    xs = x_scale.reshape(-1, 1)
    w_scale = w_scale.reshape(1, N)
    M = xq.shape[0]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    nm, nn, nk = -(-M // bm), -(-N // bn), -(-K // bk)
    pm, pn, pk = nm * bm - M, nn * bn - N, nk * bk - K
    if pm or pk:
        xq = jnp.pad(xq, ((0, pm), (0, pk)))
    if pm:
        xs = jnp.pad(xs, ((0, pm), (0, 0)), constant_values=1.0)
    if pk or pn:
        w8 = jnp.pad(w8, ((0, pk), (0, pn)))
    if pn:
        w_scale = jnp.pad(w_scale, ((0, 0), (0, pn)), constant_values=1.0)
    out = pl.pallas_call(
        functools.partial(_w8a8_matmul_kernel, nk=nk),
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, w8, xs, w_scale)
    return out[:M, :N].reshape(*lead, N)
