"""Neural-net building blocks shared by every architecture.

Functional style: ``init_*`` builds a param pytree (nested dicts of arrays),
``*_forward`` / ``*_decode`` apply it.  Per-layer params are stacked along a
leading layer dim by the model code and consumed via ``jax.lax.scan`` so the
HLO stays O(1) in depth (80 dry-run combos must compile fast).

Attention uses a pure-JAX blockwise flash implementation (two-level chunk scan
with online softmax) so 32k-token prefill never materialises an S x S score
matrix.  The Pallas TPU kernel in ``repro.kernels.flash_attention`` implements
the same math with explicit VMEM BlockSpecs; ``repro.kernels.*.ops`` selects
between them by the platform a computation is placed on.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

Params = Dict[str, Any]

# Mixed precision: params may be fp32 (training) but all layer compute runs
# in bf16 (MXU-native); norms/softmax/ssm-state internally upcast to fp32.
COMPUTE_DTYPE = jnp.bfloat16

# ----------------------------------------------------------------------------
# initialisers
# ----------------------------------------------------------------------------

def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------------------
# dense apply — the one place a projection weight meets its activations
# ----------------------------------------------------------------------------

def dense_apply(p: Params, name: str, x: jax.Array,
                act_quant: bool = False) -> jax.Array:
    """``x @ p[name]`` with the weight cast to the activation dtype — unless
    the param tree carries a ``{name}_scale`` dequant sibling (see
    ``repro.models.quantize``), in which case the projection routes through
    the fused int8 quant matmul.  Routing is purely param/flag-driven so
    quantized and float trees share every caller and every jit cache key
    shape:

    - float tree (no scale sibling)    -> plain matmul
    - quantized tree, ``act_quant`` off -> weight-only W8A16/W8A32 (int8
      weights x float activations, fp32 accumulation, weight scale applied
      once in the epilogue)
    - quantized tree, ``act_quant`` on  -> W8A8: activations dynamically
      quantized per row (symmetric absmax), int8 x int8 with int32
      accumulation, dequant once by ``act_scale x w_scale`` in the epilogue

    ``act_quant`` on a float tree is a no-op by construction (there is no
    int8 weight to contract against), so callers may thread the flag
    unconditionally."""
    scale = p.get(name + "_scale")
    if scale is None:
        return x @ p[name].astype(x.dtype)
    if act_quant:
        from repro.kernels.quant_matmul.ops import quant_matmul_w8a8
        return quant_matmul_w8a8(x, p[name], scale)
    from repro.kernels.quant_matmul.ops import quant_matmul
    return quant_matmul(x, p[name], scale)


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dtype) -> Params:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------------------------
# positions: RoPE or sinusoidal-absolute
# ----------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n_heads, head_dim); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                               # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    half = d_model // 2
    freq = 10000.0 ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------------
# attention (GQA, optional sliding window / cross attention / bidirectional)
# ----------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    hd = cfg.resolved_head_dim
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (D, H * hd), dtype),
        "wk": _dense_init(ks[1], (D, KV * hd), dtype),
        "wv": _dense_init(ks[2], (D, KV * hd), dtype),
        "wo": _dense_init(ks[3], (H * hd, D), dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x, kv_x, act_quant: bool = False):
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    q = dense_apply(p, "wq", x, act_quant=act_quant)
    k = dense_apply(p, "wk", kv_x, act_quant=act_quant)
    v = dense_apply(p, "wv", kv_x, act_quant=act_quant)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(*x.shape[:-1], H, hd)
    k = k.reshape(*kv_x.shape[:-1], KV, hd)
    v = v.reshape(*kv_x.shape[:-1], KV, hd)
    return q, k, v


def flash_attention_jnp(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, KV, hd)
    v: jax.Array,            # (B, Sk, KV, hd)
    q_pos: jax.Array,        # (Sq,) absolute positions of queries
    k_pos: jax.Array,        # (Sk,) absolute positions of keys (-1 = invalid)
    causal: bool,
    window: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    kv_mask: Optional[jax.Array] = None,   # (B, Sk) per-example key validity
) -> jax.Array:
    """Blockwise online-softmax attention, pure JAX (flash-equivalent).

    Never materialises more than (B, KV, G, q_chunk, kv_chunk) scores.
    ``kv_mask`` masks keys PER EXAMPLE (ragged batches: padded positions
    must not leak into real queries' softmax, or embeddings stop being
    invariant to how far the batch was padded — the property shape
    bucketing relies on).  ``k_pos`` stays shared across the batch.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to multiples
    nq, nk = -(-Sq // q_chunk), -(-Sk // kv_chunk)
    pq, pk = nq * q_chunk - Sq, nk * kv_chunk - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pq), constant_values=-(10 ** 9))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pk), constant_values=-1)
        if kv_mask is not None:
            kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pk)))

    # time-major xs so lax.scan slices one chunk per step (scanning over an
    # index and slicing a closured array reads the full array every step in
    # the lowered HLO — both a cost-model and a real-memory hazard)
    qg = jnp.moveaxis(q.reshape(B, nq, q_chunk, KV, G, hd), 1, 0)
    kg = jnp.moveaxis(k.reshape(B, nk, kv_chunk, KV, hd), 1, 0)
    vg = jnp.moveaxis(v.reshape(B, nk, kv_chunk, KV, hd), 1, 0)
    qp = q_pos.reshape(nq, q_chunk)
    kp = k_pos.reshape(nk, kv_chunk)
    kmg = None
    if kv_mask is not None:
        kmg = jnp.moveaxis((kv_mask != 0).reshape(B, nk, kv_chunk), 1, 0)
    scale = 1.0 / math.sqrt(hd)

    def make_q_step(qc, qpc):
        """One query chunk's online-softmax accumulation over kv chunks."""

        def kv_step(carry, kx):
            acc, m, denom = carry
            if kmg is None:
                kc, vc, kpc = kx
                kmc = None
            else:
                kc, vc, kpc, kmc = kx        # kmc: (B, kv_chunk) bool
            # bf16 operands, fp32 MXU accumulation (no upcast traffic)
            s = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            valid = kpc[None, :] >= 0
            if causal:
                valid &= kpc[None, :] <= qpc[:, None]
            if window:
                valid &= kpc[None, :] > qpc[:, None] - window
            s = jnp.where(valid[None, None, None], s, -1e30)
            if kmc is not None:
                s = jnp.where(kmc[:, None, None, None, :], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            denom = denom * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, denom), None

        return kv_step

    init = lambda: (
        jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32),
        jnp.full((B, KV, G, q_chunk), -1e30, jnp.float32),
        jnp.zeros((B, KV, G, q_chunk), jnp.float32),
    )

    def kv_xs(band=slice(None)):
        xs = (kg[band], vg[band], kp[band])
        return xs if kmg is None else xs + (kmg[band],)

    from repro.perf_flags import FLAGS

    if FLAGS.attn_band_skip and causal:
        # §Perf: statically iterate only the kv chunks inside the
        # causal/sliding-window band per q chunk (assumes contiguous
        # positions, which train/prefill provide) — the masked-out chunks
        # above the diagonal (and left of the window) are never computed.
        outs = []
        for qi in range(nq):
            hi = min(nk - 1, (qi * q_chunk + q_chunk - 1) // kv_chunk)
            lo = max(0, (qi * q_chunk - window + 1) // kv_chunk) if window else 0
            band = slice(lo, hi + 1)
            kv_step = make_q_step(qg[qi], qp[qi])
            (acc, _, denom), _ = lax.scan(kv_step, init(), kv_xs(band))
            outs.append(acc / jnp.maximum(denom[..., None], 1e-30))
        outs = jnp.stack(outs)                        # (nq, B, KV, G, qc, hd)
    else:
        def q_step(_, qx):
            qc, qpc = qx                     # (B, qc, KV, G, hd), (qc,)
            (acc, _, denom), _ = lax.scan(make_q_step(qc, qpc), init(),
                                          kv_xs())
            return None, acc / jnp.maximum(denom[..., None], 1e-30)

        _, outs = lax.scan(q_step, None, (qg, qp))    # (nq, B, KV, G, qc, hd)
    out = jnp.moveaxis(outs, 0, 1)                     # (B, nq, KV, G, qc, hd)
    out = jnp.moveaxis(out, -2, 2)                     # (B, nq, qc, KV, G, hd)
    out = out.reshape(B, nq * q_chunk, H, hd)[:, :Sq]
    return out.astype(q.dtype)


def attn_forward(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                  # (B, S, D)
    positions: jax.Array,          # (S,)
    *,
    causal: bool = True,
    kv_x: Optional[jax.Array] = None,     # cross attention source (B, Skv, D)
    kv_positions: Optional[jax.Array] = None,
    return_kv: bool = False,
    kv_mask: Optional[jax.Array] = None,  # (B, Skv) 1 = real key token
    act_quant: bool = False,              # W8A8 projections (quantized trees)
):
    """Full-sequence attention for train / prefill / encoder / cross.

    ``FLAGS.attn_kernel`` selects the implementation: the chunked pure-JAX
    flash path (baseline), or the Pallas TPU kernel
    (``repro.kernels.flash_attention``) — "auto" picks the kernel exactly
    where the computation is placed on a TPU (``repro.kernels.placement``).
    The kernel route assumes contiguous [0, S) positions (true for every
    full-sequence caller here) and turns a per-example ``kv_mask`` into
    prefix lengths, which is what the embedder's left-aligned padding
    produces.
    """
    kv_src = x if kv_x is None else kv_x
    kv_pos = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, cfg, x, kv_src, act_quant=act_quant)
    if cfg.rope_theta:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_pos, cfg.rope_theta)
    window = cfg.sliding_window if causal else 0

    def jnp_route(q, k, v, *mask):
        return flash_attention_jnp(q, k, v, positions, kv_pos, causal=causal,
                                   window=window,
                                   kv_mask=mask[0] if mask else None)

    def kernel_route(q, k, v, *mask, interpret: bool = False):
        from repro.kernels.flash_attention.ops import flash_attention
        kv_len = None
        if mask:
            kv_len = jnp.sum(mask[0] != 0, axis=-1).astype(jnp.int32)
        out = flash_attention(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
            jnp.moveaxis(v, 1, 2), causal=causal, window=window,
            backend="interpret" if interpret else "pallas", kv_len=kv_len)
        return jnp.moveaxis(out, 2, 1)

    from repro.perf_flags import FLAGS

    args = (q, k, v) + (() if kv_mask is None else (kv_mask,))
    backend = FLAGS.attn_kernel
    if backend == "auto":
        from repro.kernels.placement import by_placement
        out = by_placement(kernel_route, jnp_route, *args)
    elif backend in ("pallas", "interpret"):
        out = kernel_route(*args, interpret=backend == "interpret")
    else:
        out = jnp_route(*args)
    y = dense_apply(p, "wo", out.reshape(*x.shape[:-1], -1),
                    act_quant=act_quant)
    if return_kv:
        return y, k, v
    return y


def cache_slot(cfg: ModelConfig, pos: jax.Array, s_cache: int) -> jax.Array:
    """Which cache slot position ``pos`` writes to (ring buffer if windowed)."""
    if cfg.sliding_window:
        return pos % s_cache
    return jnp.minimum(pos, s_cache - 1)


def attn_decode_kv(p: Params, cfg: ModelConfig, x1: jax.Array, pos: jax.Array):
    """Project the current token's (rope-applied) k, v: (B, 1, KV, hd)."""
    _, k, v = _project_qkv(p, cfg, x1, x1)
    if cfg.rope_theta:
        pvec = pos[None] if pos.ndim == 0 else pos
        k = rope(k, pvec, cfg.rope_theta)
    return k, v


def attn_decode_read(
    p: Params,
    cfg: ModelConfig,
    x1: jax.Array,                 # (B, 1, D)
    pos: jax.Array,
    cache_k: jax.Array,            # (B, S_cache, KV, hd) INCLUDING current tok
    cache_v: jax.Array,
    kpos: jax.Array,               # (S_cache,) already-updated positions
):
    """Attention read against an already-updated cache slice."""
    hd = cfg.resolved_head_dim
    q = x1 @ p["wq"].astype(x1.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x1.dtype)
    B = x1.shape[0]
    H = cfg.num_heads
    q = q.reshape(B, 1, H, hd)
    if cfg.rope_theta:
        pvec = pos[None] if pos.ndim == 0 else pos
        q = rope(q, pvec, cfg.rope_theta)
    KV = cache_k.shape[2]
    G = H // KV
    qf = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, cache_k.astype(qf.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = (kpos >= 0) & (kpos <= pos)
    if cfg.sliding_window:
        valid &= kpos > pos - cfg.sliding_window
    s = jnp.where(valid[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H * hd).astype(x1.dtype) @ p["wo"].astype(x1.dtype)


def project_q(p: Params, cfg: ModelConfig, x1: jax.Array, pos: jax.Array):
    """Current token's rope-applied query: (B, H, hd)."""
    hd = cfg.resolved_head_dim
    q = x1 @ p["wq"].astype(x1.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x1.dtype)
    B = x1.shape[0]
    q = q.reshape(B, 1, cfg.num_heads, hd)
    if cfg.rope_theta:
        pvec = pos[None] if pos.ndim == 0 else pos
        q = rope(q, pvec, cfg.rope_theta)
    return q[:, 0]


def attn_decode_sharded(p: Params, cfg: ModelConfig, x1: jax.Array,
                        pos: jax.Array, cache_k, cache_v, kpos,
                        mesh, dp, seq_axes):
    """Flash-decode via shard_map: the KV cache stays sequence-sharded, each
    shard writes the new token ONLY if it owns the slot (kpos match), attends
    its local slice with a partial softmax, and the shards combine with a
    pmax/psum of (max, denom, weighted-values).

    This replaces GSPMD's lowering of dynamic-update-slice on a sharded dim,
    which rewrites the FULL cache through a select (+ copies) every layer —
    measured 1.3 TB/step on qwen2-72b decode_32k vs ~11 GB here."""
    from jax.sharding import PartitionSpec as P

    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    B = x1.shape[0]
    G = H // KV
    q = project_q(p, cfg, x1, pos).reshape(B, KV, G, hd)
    knew, vnew = attn_decode_kv(p, cfg, x1, pos)
    comb = tuple(seq_axes)
    scale = 1.0 / math.sqrt(hd)
    window = cfg.sliding_window

    def local_fn(q, knew, vnew, kl, vl, kposl, pos):
        # -- owner-shard-only cache write (tiny: (B, 1, KV, hd)) --
        eq = kposl == pos
        owner = eq.any()
        slot_l = jnp.argmax(eq).astype(jnp.int32)
        cur_k = lax.dynamic_slice_in_dim(kl, slot_l, 1, axis=1)
        cur_v = lax.dynamic_slice_in_dim(vl, slot_l, 1, axis=1)
        kl = lax.dynamic_update_slice_in_dim(
            kl, jnp.where(owner, knew.astype(kl.dtype), cur_k), slot_l, axis=1)
        vl = lax.dynamic_update_slice_in_dim(
            vl, jnp.where(owner, vnew.astype(vl.dtype), cur_v), slot_l, axis=1)
        # -- local partial softmax --
        s = jnp.einsum("bkgh,bskh->bkgs", q, kl.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        valid = (kposl >= 0) & (kposl <= pos)
        if window:
            valid &= kposl > pos - window
        s = jnp.where(valid[None, None, None], s, -1e30)
        m_l = s.max(axis=-1)                                   # (B, KV, G)
        m = lax.pmax(m_l, comb)
        pr = jnp.exp(s - m[..., None])
        pr = jnp.where(valid[None, None, None], pr, 0.0)
        den = lax.psum(pr.sum(axis=-1), comb)
        o = jnp.einsum("bkgs,bskh->bkgh", pr.astype(vl.dtype), vl,
                       preferred_element_type=jnp.float32)
        o = lax.psum(o, comb) / jnp.maximum(den[..., None], 1e-30)
        return o.astype(x1.dtype), kl, vl

    b = dp if B > 1 else None
    seq = comb if len(comb) > 1 else comb[0]
    out, nk, nv = jax.shard_map(
        local_fn, mesh=mesh, check_vma=False,
        in_specs=(P(b, None, None, None), P(b, None, None, None),
                  P(b, None, None, None), P(b, seq, None, None),
                  P(b, seq, None, None), P(seq), P()),
        out_specs=(P(b, None, None, None), P(b, seq, None, None),
                   P(b, seq, None, None)),
    )(q, knew, vnew, cache_k, cache_v, kpos, pos)
    y = out.reshape(B, 1, H * hd) @ p["wo"].astype(x1.dtype)
    return y, nk, nv


def attn_decode(
    p: Params,
    cfg: ModelConfig,
    x1: jax.Array,                 # (B, 1, D) current token's hidden
    pos: jax.Array,                # scalar int32 absolute position
    cache_k: jax.Array,            # (B, S_cache, KV, hd) rope-applied keys
    cache_v: jax.Array,
    kpos: jax.Array,               # (S_cache,) ALREADY-UPDATED position per slot
):
    """One-token decode against a (possibly ring-buffer) KV cache.

    ``kpos`` is layer-invariant, so the caller updates it once (see
    ``cache_slot``) and passes the updated array in."""
    S_cache = cache_k.shape[1]
    hd = cfg.resolved_head_dim
    q, k, v = _project_qkv(p, cfg, x1, x1)
    if cfg.rope_theta:
        pvec = pos[None] if pos.ndim == 0 else pos
        q = rope(q, pvec, cfg.rope_theta)
        k = rope(k, pvec, cfg.rope_theta)
    slot = cache_slot(cfg, pos, S_cache)
    cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), slot, axis=1)

    B, _, H, _ = q.shape
    KV = cache_k.shape[2]
    G = H // KV
    qf = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, cache_k.astype(qf.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = (kpos >= 0) & (kpos <= pos)
    if cfg.sliding_window:
        valid &= kpos > pos - cfg.sliding_window
    s = jnp.where(valid[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32)
    y = out.reshape(B, 1, H * hd).astype(x1.dtype) @ p["wo"].astype(x1.dtype)
    return y, cache_k, cache_v, kpos


def cross_decode(p: Params, cfg: ModelConfig, x1, cross_k, cross_v, kv_len):
    """Decode-time cross attention against precomputed encoder K/V."""
    hd = cfg.resolved_head_dim
    B = x1.shape[0]
    H, KV = cfg.num_heads, cfg.num_kv_heads
    G = H // KV
    q = (x1 @ p["wq"].astype(x1.dtype)).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q, cross_k.astype(q.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(cross_v.dtype), cross_v,
                     preferred_element_type=jnp.float32)
    y = out.reshape(B, 1, H * hd).astype(x1.dtype) @ p["wo"].astype(x1.dtype)
    return y


# ----------------------------------------------------------------------------
# MLP (SwiGLU or GELU)
# ----------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":
        return {
            "w_gate": _dense_init(ks[0], (D, F), dtype),
            "w_up": _dense_init(ks[1], (D, F), dtype),
            "w_down": _dense_init(ks[2], (F, D), dtype),
        }
    return {
        "w_in": _dense_init(ks[0], (D, F), dtype),
        "w_out": _dense_init(ks[1], (F, D), dtype),
    }


def apply_mlp(p: Params, cfg: ModelConfig, x: jax.Array,
              act_quant: bool = False) -> jax.Array:
    if cfg.act == "silu":
        g = jax.nn.silu(dense_apply(p, "w_gate", x, act_quant=act_quant))
        u = dense_apply(p, "w_up", x, act_quant=act_quant)
        return dense_apply(p, "w_down", g * u, act_quant=act_quant)
    h = jax.nn.gelu(dense_apply(p, "w_in", x, act_quant=act_quant))
    return dense_apply(p, "w_out", h, act_quant=act_quant)


# ----------------------------------------------------------------------------
# MoE (top-k routing, capacity-based gather dispatch — no dense one-hot einsum,
# so HLO FLOPs stay ~= useful FLOPs; see DESIGN.md §5)
# ----------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (D, E), dtype),
        "w_gate": _dense_init(ks[1], (E, D, F), dtype),
        "w_up": _dense_init(ks[2], (E, D, F), dtype),
        "w_down": _dense_init(ks[3], (E, F, D), dtype),
    }


def apply_moe(p: Params, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss)."""
    from repro.perf_flags import FLAGS

    if FLAGS.moe_row_dispatch:
        return _apply_moe_row(p, cfg, x)
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xf = x.reshape(T, D)
    logits = (xf @ p["router"].astype(xf.dtype)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eidx = lax.top_k(probs, K)                                  # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style): E * sum(frac_tokens * frac_probs)
    me = probs.mean(axis=0)                                             # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    capacity = int(math.ceil(T * K / E * cfg.capacity_factor))
    # position of each (token, k) assignment inside its expert's queue
    flat_e = eidx.reshape(-1)                                           # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)                 # (T*K, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1             # (T*K,)
    keep = pos < capacity
    slot = jnp.where(keep, flat_e * capacity + pos, E * capacity)       # overflow slot

    xr = jnp.repeat(xf, K, axis=0)                                      # (T*K, D)
    dispatched = jnp.zeros((E * capacity + 1, D), xf.dtype).at[slot].set(xr)
    ein = dispatched[: E * capacity].reshape(E, capacity, D)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, p["w_gate"].astype(ein.dtype)))
    u = jnp.einsum("ecd,edf->ecf", ein, p["w_up"].astype(ein.dtype))
    eout = jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(ein.dtype))

    eflat = jnp.concatenate([eout.reshape(E * capacity, D),
                             jnp.zeros((1, D), eout.dtype)], axis=0)
    gathered = eflat[slot]                                              # (T*K, D)
    w = (gate_w.reshape(-1) * keep.astype(jnp.float32)).astype(gathered.dtype)
    y = (gathered * w[:, None]).reshape(T, K, D).sum(axis=1)
    return y.reshape(B, S, D), aux


def _mesh_axis_names():
    """Axis names of the mesh currently in context, () if none."""
    am = jax.sharding.get_abstract_mesh()
    return () if am.empty else tuple(am.axis_names)


def _moe_constrain(x: jax.Array, tail_spec) -> jax.Array:
    """with_sharding_constraint(P(dp, *tail_spec)) when a mesh is in context
    (launchers wrap lowering in a mesh context); no-op otherwise."""
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in _mesh_axis_names() if a in ("pod", "data"))
    if not dp:
        return x
    b = dp if len(dp) > 1 else dp[0]
    return lax.with_sharding_constraint(x, P(b, *tail_spec))


def _apply_moe_row(p: Params, cfg: ModelConfig, x: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """§Perf MoE dispatch: bucket tokens per BATCH ROW so scatter/gather
    indices never cross the data-sharded batch dim.  The global-scatter
    baseline makes GSPMD all-gather the full (T*K, D) token array to every
    device (the dominant collective on qwen3-moe train_4k); here the batch
    dim stays sharded end-to-end and the expert einsums shard (B->data,
    E->model) with no token gather."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = (x @ p["router"].astype(x.dtype)).astype(jnp.float32)      # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eidx = lax.top_k(probs, K)                                  # (B,S,K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / (B * S * K)
    aux = E * jnp.sum(me * ce)

    cap = int(math.ceil(S * K / E * cfg.capacity_factor))
    flat_e = eidx.reshape(B, S * K)                                     # row-local
    # Position of each assignment within its expert's queue via sort-based
    # ranking: all intermediates are (B, S*K) or (B, E) — the one-hot-cumsum
    # formulation materialises (B, S*K, E) (4.3 GB/layer at this scale).
    # Every gather/scatter below goes through take/put_along_axis so GSPMD
    # sees BATCHED operations (batch dim stays data-sharded, no cross-device
    # combine); explicit row-index advanced indexing lowers to unbatched
    # gathers that GSPMD finishes with full-array all-reduces (measured
    # 1.2 TB/step of collectives on qwen3-moe train_4k).
    rows = jnp.arange(B)[:, None]
    counts = jnp.zeros((B, E), jnp.int32).at[rows, flat_e].add(1)       # (B,E)
    starts = jnp.cumsum(counts, axis=1) - counts                        # exclusive
    order = jnp.argsort(flat_e, axis=1, stable=True)                    # (B,S*K)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    pos_sorted = (jnp.arange(S * K, dtype=jnp.int32)[None]
                  - jnp.take_along_axis(starts, sorted_e, axis=1))
    pos = jnp.put_along_axis(jnp.zeros((B, S * K), jnp.int32), order,
                             pos_sorted, axis=1, inplace=False)
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, E * cap)                 # (B,S*K)

    xr = jnp.repeat(x.reshape(B, S, D), K, axis=1)                      # (B,S*K,D)
    # vmap'd per-row scatter -> HLO scatter with operand_batching_dims
    dispatched = jax.vmap(
        lambda s, v: jnp.zeros((E * cap + 1, D), x.dtype).at[s].set(v)
    )(slot, xr)
    # pin the token-major layout (B->data, D->model): the scatter stays
    # local; GSPMD then resharding into the expert einsum's (E->model)
    # layout is one all-to-all instead of a full-array all-reduce combine
    dispatched = _moe_constrain(dispatched, (None, "model"))
    ein = dispatched[:, : E * cap].reshape(B, E, cap, D)

    g = jax.nn.silu(jnp.einsum("becd,edf->becf", ein,
                               p["w_gate"].astype(ein.dtype)))
    u = jnp.einsum("becd,edf->becf", ein, p["w_up"].astype(ein.dtype))
    eout = jnp.einsum("becf,efd->becd", g * u, p["w_down"].astype(ein.dtype))

    eflat = jnp.concatenate([eout.reshape(B, E * cap, D),
                             jnp.zeros((B, 1, D), eout.dtype)], axis=1)
    # reshard expert-major -> token-major BEFORE the combine gather so the
    # gather itself is fully local (batched over B, slot dim replicated)
    eflat = _moe_constrain(eflat, (None, "model"))
    gathered = jnp.take_along_axis(eflat, slot[..., None], axis=1)      # (B,S*K,D)
    w = (gate_w.reshape(B, S * K) * keep.astype(jnp.float32)
         ).astype(gathered.dtype)
    y = (gathered * w[..., None]).reshape(B, S, K, D).sum(axis=2)
    return y, aux


# ----------------------------------------------------------------------------
# Mamba-1 selective scan
# ----------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig, dtype) -> Params:
    D, DI, N, R, CK = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank, cfg.ssm_conv
    ks = jax.random.split(key, 6)
    return {
        "in_proj": _dense_init(ks[0], (D, 2 * DI), dtype),
        "conv_w": _dense_init(ks[1], (CK, DI), dtype, scale=1.0 / math.sqrt(CK)),
        "conv_b": jnp.zeros((DI,), dtype),
        "x_proj": _dense_init(ks[2], (DI, R + 2 * N), dtype),
        "dt_proj": _dense_init(ks[3], (R, DI), dtype),
        "dt_bias": jnp.full((DI,), math.log(math.e - 1), dtype),  # softplus^-1(1)
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32),
                                          (DI, N))).astype(jnp.float32),
        "D": jnp.ones((DI,), jnp.float32),
        "out_proj": _dense_init(ks[4], (DI, D), dtype),
    }


def _mamba_core(p: Params, cfg: ModelConfig, xz: jax.Array, conv_state=None):
    """Shared pre-scan computation.  xz: (B, S, 2*DI)."""
    DI, N, R = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    x, z = jnp.split(xz, 2, axis=-1)                          # (B, S, DI)
    # causal depthwise conv along S (kernel CK)
    CK = cfg.ssm_conv
    if conv_state is None:
        xpad = jnp.pad(x, ((0, 0), (CK - 1, 0), (0, 0)))
    else:
        xpad = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    new_conv_state = xpad[:, -(CK - 1):, :]
    conv_w = p["conv_w"].astype(x.dtype)
    xc = sum(xpad[:, i : i + x.shape[1], :] * conv_w[i] for i in range(CK))
    xc = jax.nn.silu(xc + p["conv_b"].astype(x.dtype))
    # input-dependent SSM params
    dbc = xc @ p["x_proj"].astype(xc.dtype)                   # (B, S, R+2N)
    dt, Bm, Cm = jnp.split(dbc, [R, R + N], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"].astype(dt.dtype)
                         + p["dt_bias"].astype(dt.dtype)).astype(jnp.float32)
    A = -jnp.exp(p["A_log"])                                   # (DI, N)
    return xc, z, dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32), A, new_conv_state


def mamba_scan_ref(xc, dt, Bm, Cm, A, h0=None):
    """Sequential selective scan.  xc: (B,S,DI) dt: (B,S,DI) Bm/Cm: (B,S,N).

    Returns (y: (B,S,DI) fp32, h_final: (B,DI,N) fp32)."""
    B, S, DI = xc.shape
    N = Bm.shape[-1]
    h0 = jnp.zeros((B, DI, N), jnp.float32) if h0 is None else h0
    xf = xc.astype(jnp.float32)

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp                              # time-major xs
        dA = jnp.exp(dt_t[..., None] * A)                      # (B, DI, N)
        dBx = (dt_t * x_t)[..., None] * b_t[:, None, :]
        h = h * dA + dBx
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (dt, xf, Bm, Cm))
    h, ys = lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h                           # (B,S,DI), (B,DI,N)


def mamba_scan_chunked(xc, dt, Bm, Cm, A, h0=None, chunk: int = 16):
    """Time-chunked selective scan: outer lax.scan over S/chunk chunks with a
    ``jax.checkpoint``-ed unrolled inner body.

    The win is in the BACKWARD: differentiating a per-timestep scan stores
    O(S) copies of (B, DI, N)-sized residuals (measured ~8 buffers = 105
    GB/layer on hymba train_4k); checkpointing at chunk granularity stores
    only the chunk-boundary carries (S/chunk of them) and recomputes inside
    the chunk — the time analogue of remat-over-layers."""
    B, S, DI = xc.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    nc = S // chunk
    h0 = jnp.zeros((B, DI, N), jnp.float32) if h0 is None else h0
    xf = xc.astype(jnp.float32)

    def to_chunks(a):
        return jnp.moveaxis(a.reshape(B, nc, chunk, -1), 1, 0)

    xs = tuple(to_chunks(a) for a in (dt, xf, Bm, Cm))

    @jax.checkpoint
    def outer(h, inp):
        dts, xcs, bs, cs = inp              # (B, chunk, DI/ N)
        ys = []
        for t in range(chunk):              # unrolled: fused by XLA
            dA = jnp.exp(dts[:, t][..., None] * A)
            h = h * dA + (dts[:, t] * xcs[:, t])[..., None] * bs[:, t][:, None, :]
            ys.append(jnp.einsum("bdn,bn->bd", h, cs[:, t]))
        return h, jnp.stack(ys, axis=1)     # (B, chunk, DI)

    h, ys = lax.scan(outer, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, DI)
    return y, h


def default_mamba_scan():
    from repro.perf_flags import FLAGS

    if FLAGS.mamba_chunk > 0:
        return functools.partial(mamba_scan_chunked, chunk=FLAGS.mamba_chunk)
    return mamba_scan_ref


def mamba_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                  scan_fn=None) -> jax.Array:
    """Full-sequence mamba mixer.  scan_fn lets the kernel layer substitute the
    Pallas chunked scan; defaults per perf_flags (baseline: sequential)."""
    scan_fn = scan_fn or default_mamba_scan()
    xz = x @ p["in_proj"].astype(x.dtype)
    xc, z, dt, Bm, Cm, A, _ = _mamba_core(p, cfg, xz)
    y, _ = scan_fn(xc, dt, Bm, Cm, A)
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return y @ p["out_proj"].astype(x.dtype)


def mamba_prefill(p: Params, cfg: ModelConfig, x: jax.Array):
    """Like mamba_forward but also returns (ssm_state, conv_state) for decode."""
    xz = x @ p["in_proj"].astype(x.dtype)
    xc, z, dt, Bm, Cm, A, conv_state = _mamba_core(p, cfg, xz)
    y, h = default_mamba_scan()(xc, dt, Bm, Cm, A)
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return y @ p["out_proj"].astype(x.dtype), h, conv_state


def mamba_decode(p: Params, cfg: ModelConfig, x1: jax.Array,
                 ssm_state: jax.Array, conv_state: jax.Array):
    """One-token recurrent step.  x1: (B,1,D); ssm_state: (B,DI,N) fp32;
    conv_state: (B, CK-1, DI)."""
    xz = x1 @ p["in_proj"].astype(x1.dtype)
    xc, z, dt, Bm, Cm, A, new_conv = _mamba_core(p, cfg, xz, conv_state=conv_state)
    # S == 1: single recurrence step
    dA = jnp.exp(dt[:, 0][..., None] * A)
    dBx = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * Bm[:, 0][:, None, :]
    h = ssm_state * dA + dBx
    y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0])[:, None, :]
    y = y + p["D"] * xc.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x1.dtype)
    return y @ p["out_proj"].astype(x1.dtype), h, new_conv
