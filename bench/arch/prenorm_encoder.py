"""The pre-LN encoder block that the program serves for bge-large-zh-v1.5.

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
from typing import Dict

# the program's initialisation that ``seeded_init`` wraps
PROGRAM_INIT = "repro.models.embedder.init_embedder"
# the CPU tests' cut: every layer is the same block, so any depth holds a
# whole period; two layers keep the scan over layers, every width is cut,
# and attention stays multi-head
SMALL = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
             head_dim=32, intermediate_size=256, vocab_size=512)

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


def forward(cfg: dict):
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


def encoder_flops(length: int, cfg: dict) -> int:
    """Matmul operations of one sequence of ``length`` real tokens: per
    layer the Q, K, V and output projections, the two FFN matmuls, and the
    attention scores and weighted values over the sequence's own tokens."""
    d = cfg["hidden_size"]
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    ffn = cfg["intermediate_size"]
    proj = 2 * length * d * inner * 4             # q, k, v, o
    mlp = 2 * length * d * ffn * 2                # in, out
    attn = 2 * length * length * inner * 2        # q.k^T and p.v
    return cfg["num_hidden_layers"] * (proj + mlp + attn)


def program_fields(cfg: dict) -> dict:
    """The program's configuration, size for size, that serves this block."""
    return {"num_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_attention_heads"],
            "resolved_head_dim": cfg["head_dim"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "norm_eps": cfg["layer_norm_eps"],
            "pool": cfg["pooling"], "act": "gelu", "norm": "layernorm"}


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
