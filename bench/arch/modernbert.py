"""ModernBERT's encoder (arXiv:2412.13663; ``answerdotai/ModernBERT-large``
config.json and its reference modelling code), served mean-pooled.

Written from the configuration file alone (``bench/configs/<name>.json``),
importing nothing of the program:

    h_0 = LN_e(E[tokens])                       (no absolute positions)
    layer i (global when i % every == 0, local otherwise):
        a = h if i == 0 else LN_a(h)
        q, k, v = RoPE_theta(a W_q), RoPE_theta(a W_k), a W_v
        h = h + Attn(q, k, v) W_o
        [x, g] = LN_m(h) W_i                    (W_i: d -> 2 x ffn)
        h = h + (GELU_erf(x) * g) W_o'
    out = L2normalise(mean over real tokens of LN_f(h))

A global layer attends every real key, with theta ``global_rope_theta``; a
local one the real keys with |i - j| <= ``local_attention`` // 2, with
theta ``local_rope_theta``.  RoPE rotates half: (x1, x2) -> (x1 cos -
x2 sin, x2 cos + x1 sin), frequencies theta^(-2m / head_dim).  No
projection or norm has a bias, and every norm's scale is 1 as the program
initialises it, so ``LN`` here is the bare normalisation.

Attention runs by blocks of queries (``QUERY_BLOCK``): a global block
against every key, a local block against the keys of its band only, so one
8,192-token input fits in float32 and local layers cost their band.

Weights are remade from the run's seed by the scheme the benchmark serves,
restated here: ``jax.random.PRNGKey(seed)`` split into three keys (layers,
embedding table, unused); the layer key split once per layer, each layer
key into an attention and an FFN key, those into 4 and 3 keys for W_q,
W_k, W_v, W_o and W_i, W_o' (the third unused); projections drawn
normal / sqrt(fan_in), the table normal * ``embedding_init_std``.  Every
matmul runs at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import math
from typing import Dict

# the program's initialisation that ``seeded_init`` wraps
PROGRAM_INIT = "repro.models.embedder.init_embedder"
# the CPU tests' cut: layer 0 and one whole period after it (local, local,
# global), every width cut, a band narrower than the tests' inputs, and a
# served context that holds them
SMALL = dict(num_hidden_layers=4, hidden_size=128, num_attention_heads=4,
             head_dim=32, intermediate_size=256, vocab_size=512,
             local_attention=16, max_position_embeddings=256)
# queries per block of the reference's attention
QUERY_BLOCK = 512

HIGHEST = None      # set on first use: jax.lax.Precision.HIGHEST


def _jax():
    global HIGHEST
    import jax
    import jax.numpy as jnp

    HIGHEST = jax.lax.Precision.HIGHEST
    return jax, jnp


def half_window(cfg: dict) -> int:
    return cfg["local_attention"] // 2


def is_global(cfg: dict, i: int) -> bool:
    return i % cfg["global_attn_every_n_layers"] == 0


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
                "w_in": normal(f[0], (d, 2 * ffn), s_d),
                "w_out": normal(f[1], (ffn, d), s_f)}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 3)
        layers = jax.vmap(layer)(jax.random.split(ks[0], L))
        return {"embed": normal(ks[1], (vocab, d), cfg["embedding_init_std"]),
                "layers": layers}

    return make(jax.random.PRNGKey(seed))


def forward(cfg: dict):
    """jitted (weights, tokens (B, S), mask (B, S)) -> (B, d) unit vectors."""
    jax, jnp = _jax()
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    w = half_window(cfg)
    L = cfg["num_hidden_layers"]
    if cfg["pooling"] != "mean":
        raise ValueError(f"ModernBERT is served mean-pooled, not "
                         f"{cfg['pooling']!r}")

    def mm(x, y):
        return jnp.matmul(x, y, precision=HIGHEST)

    def ln(x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)

    def gelu_erf(x):
        return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))

    def rope(x, theta):                   # x: (B, S, H, hd)
        S = x.shape[1]
        inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        f = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
        emb = jnp.concatenate([f, f], axis=-1)[None, :, None, :]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return x * jnp.cos(emb) + rot * jnp.sin(emb)

    def attend(q, k, v, kmask, rel):
        """q: (B, Q, H, hd) at positions p0 + [0, Q); k, v: (B, K, H, hd);
        kmask (B, K) real keys; rel (Q, K) keys in the query's band."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision=HIGHEST) / math.sqrt(hd)
        ok = rel[None, None] & (kmask[:, None, None, :] > 0)
        # a finite floor: a padding position whose band holds no real key
        # stays finite (and is never pooled) instead of poisoning p.v
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    def attention(q, k, v, mask, local):
        B, S = mask.shape
        Q = min(QUERY_BLOCK, S)
        n = -(-S // Q)
        q = jnp.pad(q, ((0, 0), (0, n * Q - S), (0, 0), (0, 0)))
        blocks = jnp.arange(n) * Q
        if local:
            # the keys of block [c, c + Q) lie in [c - w, c + Q + w)
            pad = ((0, 0), (w, n * Q - S + w), (0, 0), (0, 0))
            kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)
            mp = jnp.pad(mask, ((0, 0), (w, n * Q - S + w)))
            rel = jnp.abs(jnp.arange(Q + 2 * w)[None, :] - w
                          - jnp.arange(Q)[:, None]) <= w

            def one(c):
                qs = jax.lax.dynamic_slice_in_dim(q, c, Q, 1)
                ks = jax.lax.dynamic_slice_in_dim(kp, c, Q + 2 * w, 1)
                vs = jax.lax.dynamic_slice_in_dim(vp, c, Q + 2 * w, 1)
                ms = jax.lax.dynamic_slice_in_dim(mp, c, Q + 2 * w, 1)
                return attend(qs, ks, vs, ms, rel)
        else:
            rel = jnp.ones((Q, S), bool)

            def one(c):
                qs = jax.lax.dynamic_slice_in_dim(q, c, Q, 1)
                return attend(qs, k, v, mask, rel)

        out = jax.lax.map(one, blocks)            # (n, B, Q, H, hd)
        return jnp.moveaxis(out, 0, 1).reshape(B, n * Q, H, hd)[:, :S]

    def layer(h, x, mask):
        i, wts = x
        B, S, _ = h.shape
        a = jnp.where(i == 0, h, ln(h))
        q = mm(a, wts["wq"]).reshape(B, S, H, hd)
        k = mm(a, wts["wk"]).reshape(B, S, H, hd)
        v = mm(a, wts["wv"]).reshape(B, S, H, hd)

        def attn(local):
            theta = cfg["local_rope_theta" if local else "global_rope_theta"]
            return attention(rope(q, theta), rope(k, theta), v, mask, local)

        o = jax.lax.cond(i % cfg["global_attn_every_n_layers"] == 0,
                         lambda: attn(False), lambda: attn(True))
        h = h + mm(o.reshape(B, S, H * hd), wts["wo"])
        x_in, gate = jnp.split(mm(ln(h), wts["w_in"]), 2, axis=-1)
        return h + mm(gelu_erf(x_in) * gate, wts["w_out"]), None

    @jax.jit
    def forward(weights, tokens, mask):
        h = ln(weights["embed"][tokens])
        h, _ = jax.lax.scan(lambda h, x: layer(h, x, mask), h,
                            (jnp.arange(L), weights["layers"]))
        h = ln(h)
        pooled = (h * mask[..., None]).sum(1) / mask.sum(1, keepdims=True)
        return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)

    return forward


def band_keys(length: int, w: int) -> int:
    """(query, key) pairs of ``length`` tokens with |i - j| <= ``w``: query
    i attends min(length - 1, i + w) - max(0, i - w) + 1 keys."""
    m = min(w, length - 1)
    return length + 2 * (m * length - m * (m + 1) // 2)


def layer_kinds(cfg: dict) -> Dict[str, int]:
    """Number of global and local layers."""
    L = cfg["num_hidden_layers"]
    n_global = sum(is_global(cfg, i) for i in range(L))
    return {"global": n_global, "local": L - n_global}


def attended_keys(length: int, cfg: dict) -> Dict[str, int]:
    """Keys the real tokens of one input attend, per layer of each kind."""
    return {"global": length * length,
            "local": band_keys(length, half_window(cfg))}


def encoder_flops(length: int, cfg: dict) -> int:
    """Matmul operations of one sequence of ``length`` real tokens: per
    layer the Q, K, V and output projections, the GeGLU FFN's three
    matmuls (W_i to twice the width, W_o' back), and the attention scores
    and weighted values over the keys each query attends: every key on a
    global layer, the band's on a local one."""
    d = cfg["hidden_size"]
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    ffn = cfg["intermediate_size"]
    proj = 2 * length * d * inner * 4             # q, k, v, o
    mlp = 2 * length * d * ffn * 3                # input, gate, out
    keys = attended_keys(length, cfg)
    attn = sum(n * 2 * 2 * inner * keys[kind]     # q.k^T and p.v
               for kind, n in layer_kinds(cfg).items())
    return cfg["num_hidden_layers"] * (proj + mlp) + attn


def program_fields(cfg: dict) -> dict:
    """The program's configuration, size for size, that serves this block."""
    return {"num_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_attention_heads"],
            "resolved_head_dim": cfg["head_dim"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "norm_eps": cfg["norm_eps"],
            "pool": cfg["pooling"], "act": "geglu", "norm": "layernorm",
            "norm_bias": cfg["norm_bias"], "embed_norm": True,
            "global_every": cfg["global_attn_every_n_layers"],
            "local_window": half_window(cfg),
            "rope_theta": cfg["global_rope_theta"],
            "local_rope_theta": cfg["local_rope_theta"],
            "max_tokens": cfg["max_position_embeddings"],
            "probe_tokens": cfg["max_position_embeddings"]}


def seeded_init(init, std: float):
    """The program's ``init_embedder`` with the token table drawn anew as
    normal * ``std`` from the table's own key, as ``init_weights`` draws
    it; every other leaf is the program's."""
    import jax
    import jax.numpy as jnp

    def init_params(key, pcfg, dtype=jnp.float32):
        params = init(key, pcfg, dtype)
        table = jax.random.split(key, 3)[1]
        params["embed"] = (jax.random.normal(
            table, params["embed"].shape, jnp.float32) * std).astype(dtype)
        return params

    return init_params
