"""Bidirectional text embedder — the paper's own model family.

bge-large-zh-v1.5 (326M, CLS pooling, 1024-d output) style: BERT-like
encoder stack + pooling + L2 normalisation; and ModernBERT-large, whose
stack alternates global and banded-local attention layers with a rotary
theta each.  This is the model WindVE serves; its forward pass is what the
queue manager's CPU/NPU instances execute per batch of queries.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels.pool_norm import pool_norm
from repro.models import layers as L

Params = Dict[str, Any]


def init_embedder(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)

    def blk(k):
        k1, k2 = jax.random.split(k)
        return {
            "norm1": L.init_norm(cfg, dtype),
            "attn": L.init_attention(k1, cfg, dtype),
            "norm2": L.init_norm(cfg, dtype),
            "ffn": L.init_mlp(k2, cfg, dtype),
        }

    params = {
        "embed": L._dense_init(ks[1], (cfg.vocab_size, cfg.d_model), dtype, scale=0.02),
        "blocks": jax.vmap(blk)(layer_keys),
        "final_norm": L.init_norm(cfg, dtype),
    }
    if cfg.embed_norm:
        # layer 0's ``norm1`` stays in the stack, unused: this norm is it
        params["embed_norm"] = L.init_norm(cfg, dtype)
    return params


def band_pairs(n: int, w: int) -> int:
    """(query, key) pairs of an ``n``-token input with |i - j| <= ``w``."""
    m = min(w, n - 1)
    return n + 2 * (m * n - m * (m + 1) // 2) if n > 0 else 0


def attended_keys(cfg: ModelConfig, lengths) -> Dict[str, int]:
    """Key positions the real tokens of inputs of these lengths attend to,
    summed over the layers of each kind: ``global`` layers attend every
    real key, ``local`` ones the keys of their band."""
    kinds = [cfg.is_global_layer(i) for i in range(cfg.num_layers)]
    n_global = sum(kinds)
    out = {"global": n_global * sum(int(n) ** 2 for n in lengths)}
    if n_global < cfg.num_layers:
        out["local"] = (cfg.num_layers - n_global) * sum(
            band_pairs(int(n), cfg.local_window) for n in lengths)
    return out


def embed(params: Params, cfg: ModelConfig, tokens: jax.Array,
          mask: jax.Array | None = None, *,
          compute_dtype: Any = None, act_quant: bool = False) -> jax.Array:
    """tokens: (B, S) int32; mask: (B, S) 1=real token.  Returns (B, embed_dim)
    L2-normalised embeddings (the paper's 1024-d fp32 output vector).

    The mask is honoured END TO END: padded positions are excluded from every
    attention softmax (``kv_mask``), not just from pooling, so an embedding
    is invariant to how far its batch was padded — the property that lets
    the shape-bucketed backend (``repro.core.bucketing``) pad to the bucket
    instead of the global max and still serve identical vectors.  The
    pooling + L2-normalise epilogue runs through the fused
    ``repro.kernels.pool_norm`` op (Pallas kernel on TPU, jnp oracle here)
    and accumulates in fp32 for ANY compute dtype, so served vectors are
    always fp32 unit vectors.

    ``compute_dtype`` pins the trunk's activation dtype (every weight is cast
    to the activation dtype at use, see ``models.layers``): the serving
    backends pass ``jnp.float32`` for the precision oracle and
    ``jnp.bfloat16`` for bf16-resident serving; None keeps the global
    ``layers.COMPUTE_DTYPE`` default.  ``act_quant`` turns on W8A8
    projections (dynamic per-row int8 activation quantization against an
    int8-quantized param tree — see ``models.layers.dense_apply``); it is a
    no-op on float trees, and the pool epilogue stays fp32 regardless.
    """
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    cdt = L.COMPUTE_DTYPE if compute_dtype is None else compute_dtype
    h = params["embed"][tokens].astype(cdt)
    if not cfg.rope_theta:       # absolute positions; else rotary per layer
        h = h + L.sinusoidal_positions(positions, cfg.d_model).astype(h.dtype)
    kv_mask = mask          # None -> every position is a real token

    def layer(h, bp, is_global: bool, first: bool = False):
        hin = h if first else L.apply_norm(bp["norm1"], cfg, h)
        with jax.named_scope("attn_global" if is_global else "attn_local"):
            h = h + L.attn_forward(
                bp["attn"], cfg, hin, positions, causal=False,
                kv_mask=kv_mask, act_quant=act_quant,
                window=0 if is_global else cfg.local_window,
                theta=cfg.rope_theta if is_global else cfg.local_rope_theta)
        hin = L.apply_norm(bp["norm2"], cfg, h)
        h = h + L.apply_mlp(bp["ffn"], cfg, hin, act_quant=act_quant)
        return h

    def at(tree, i):
        return jax.tree.map(lambda x: x[i], tree)

    blocks = params["blocks"]
    first = 0
    if cfg.embed_norm:      # the embedding norm stands in for layer 0's
        h = L.apply_norm(params["embed_norm"], cfg, h)
        h = layer(h, at(blocks, 0), cfg.is_global_layer(0), first=True)
        first = 1
    # the layers after ``first`` repeat a period of ``global_every`` kinds:
    # one scan step runs a whole period, so the stack holds every kind of
    # block and its HLO stays O(1) in depth
    period = cfg.global_every or 1
    kinds = [cfg.is_global_layer(first + j) for j in range(period)]
    groups = (cfg.num_layers - first) // period
    if period == 1:
        rest = blocks if not first else at(blocks, slice(first, None))
        h, _ = lax.scan(lambda h, bp: (layer(h, bp, kinds[0]), None), h,
                        rest)
    elif groups:
        stop = first + groups * period
        grouped = jax.tree.map(
            lambda x: x[first:stop].reshape(groups, period, *x.shape[1:]),
            blocks)

        def body(h, gp):
            for j, is_global in enumerate(kinds):
                h = layer(h, at(gp, j), is_global)
            return h, None

        h, _ = lax.scan(body, h, grouped)
    for i in range(first + groups * period, cfg.num_layers):
        h = layer(h, at(blocks, i), cfg.is_global_layer(i))
    h = L.apply_norm(params["final_norm"], cfg, h)

    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    return pool_norm(h, mask, pool="mean" if cfg.pool == "mean" else "cls")
