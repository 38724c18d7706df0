"""The plain float32 reference of the served encoder.

Written from the configuration file alone (``bench/configs/<name>.json``),
importing nothing of the program.  It follows the equations the program
serves today, which depart from the published models as each
configuration's ``assumed.departures`` lists:

    h_0 = E[tokens] + P,  P = [sin(pos * f), cos(pos * f)],
                          f_i = 10000 ** (-i / (d / 2))
    per layer (pre-LN):
        a = LN_1(h);  h = h + Attn(a W_q, a W_k, a W_v) W_o
        b = LN_2(h);  h = h + GELU_tanh(b W_in) W_out
    out = L2normalise(pool(LN_f(h)))

Attention is bidirectional over the query's real tokens only (padded keys
are masked out).  ``pool`` is the first token (``cls``) or the mean over
real tokens (``mean``).  Projections carry no bias.  Every LayerNorm's
scale is 1 and its bias 0 as the program initialises them, so ``LN`` here
is the bare normalisation.

Weights are remade from the run's seed by the scheme the benchmark serves,
restated here: ``jax.random.PRNGKey(seed)`` split into three keys
(layers, embedding table, unused); the layer key split once per layer,
each layer key into an attention and an FFN key, those into 4 and 3 keys
for W_q, W_k, W_v, W_o and W_in, W_out; projections drawn
normal / sqrt(fan_in), the table normal * ``embedding_init_std``,
LayerNorm scale 1 and bias 0.  Every matmul runs at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

HIGHEST = None      # set on first use: jax.lax.Precision.HIGHEST


def _jax():
    global HIGHEST
    import jax
    import jax.numpy as jnp

    HIGHEST = jax.lax.Precision.HIGHEST
    return jax, jnp


def init_weights(cfg: dict, seed: int) -> Dict:
    """float32 weights of the configuration, remade from ``seed``."""
    jax, jnp = _jax()
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    ffn, vocab = cfg["intermediate_size"], cfg["vocab_size"]

    def normal(k, shape, scale):
        # the barrier keeps XLA from folding the scale into the sampler, so
        # the product rounds as the program's eager one does
        z = jax.lax.optimization_barrier(
            jax.random.normal(k, shape, jnp.float32))
        return z * scale

    def layer(k):
        ka, kf = jax.random.split(k)
        a = jax.random.split(ka, 4)
        f = jax.random.split(kf, 3)
        s_d, s_i, s_f = (1.0 / math.sqrt(d), 1.0 / math.sqrt(inner),
                         1.0 / math.sqrt(ffn))
        return {"wq": normal(a[0], (d, inner), s_d),
                "wk": normal(a[1], (d, inner), s_d),
                "wv": normal(a[2], (d, inner), s_d),
                "wo": normal(a[3], (inner, d), s_i),
                "w_in": normal(f[0], (d, ffn), s_d),
                "w_out": normal(f[1], (ffn, d), s_f)}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 3)
        layers = jax.vmap(layer)(jax.random.split(ks[0], L))
        return {"embed": normal(ks[1], (vocab, d), cfg["embedding_init_std"]),
                "layers": layers}

    return make(jax.random.PRNGKey(seed))


def _forward(cfg: dict):
    """jitted (weights, tokens (B, S), mask (B, S)) -> (B, d) unit vectors."""
    jax, jnp = _jax()
    d, H, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    eps = cfg["layer_norm_eps"]
    mean_pool = cfg["pooling"] == "mean"
    if cfg["pooling"] not in ("cls", "mean"):
        raise ValueError(f"unknown pooling {cfg['pooling']!r}")

    def mm(x, w):
        return jnp.matmul(x, w, precision=HIGHEST)

    def ln(x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)

    def gelu_tanh(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    def layer(h, w, mask):
        B, S, _ = h.shape
        a = ln(h)
        q = mm(a, w["wq"]).reshape(B, S, H, hd)
        k = mm(a, w["wk"]).reshape(B, S, H, hd)
        v = mm(a, w["wv"]).reshape(B, S, H, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(mask[:, None, None, :] > 0, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
        h = h + mm(o.reshape(B, S, H * hd), w["wo"])
        b = ln(h)
        return h + mm(gelu_tanh(mm(b, w["w_in"])), w["w_out"])

    @jax.jit
    def forward(weights, tokens, mask):
        S = tokens.shape[1]
        pos = jnp.arange(S, dtype=jnp.float32)
        f = 10000.0 ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
        ang = pos[:, None] * f
        h = weights["embed"][tokens] + jnp.concatenate(
            [jnp.sin(ang), jnp.cos(ang)], axis=-1)
        h, _ = jax.lax.scan(lambda h, w: (layer(h, w, mask), None), h,
                            weights["layers"])
        h = ln(h)
        if mean_pool:
            pooled = (h * mask[..., None]).sum(1) / mask.sum(1, keepdims=True)
        else:
            pooled = h[:, 0]
        return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)

    return forward


def embed(cfg: dict, seed: int, queries: Sequence[np.ndarray],
          block: int = 32, weights=None) -> np.ndarray:
    """Reference embeddings of token-id arrays, ``block`` rows at a time,
    every block padded to the longest query of the set."""
    jax, jnp = _jax()
    if weights is None:
        weights = init_weights(cfg, seed)
    fwd = _forward(cfg)
    S = max(len(q) for q in queries)
    out: List[np.ndarray] = []
    for i in range(0, len(queries), block):
        part = queries[i:i + block]
        toks = np.zeros((block, S), np.int32)
        mask = np.zeros((block, S), np.float32)
        mask[:, 0] = 1.0            # filler rows keep one real token
        for r, q in enumerate(part):
            toks[r, :len(q)] = q
            mask[r, :len(q)] = 1.0
        with jax.default_matmul_precision("highest"):
            e = fwd(weights, jnp.asarray(toks), jnp.asarray(mask))
        out.append(np.asarray(e)[:len(part)])
    return np.concatenate(out)


def l2_gaps(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-query L2 distance between served and reference unit vectors."""
    return np.linalg.norm(np.asarray(served, np.float64)
                          - np.asarray(ref, np.float64), axis=-1)
