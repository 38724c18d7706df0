"""ModernBERT-large — a long-context bidirectional encoder (395M) that
alternates banded-local and global attention [arXiv:2412.13663;
``answerdotai/ModernBERT-large`` config.json].

Every third layer, from layer 0, attends over the whole input with rotary
theta 160,000; the others attend over |i - j| <= 64 (``local_attention``
128) with theta 10,000.  GeGLU FFN (``Wi`` 1,024 -> 2 x 2,624), no bias on
any projection or norm, a LayerNorm on the embeddings in place of layer
0's attention norm, 8,192 positions.  Served mean-pooled, as the
ModernBERT-based embedders are."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="modernbert-large",
    arch_type="encoder",
    block="attn",
    num_layers=28,
    d_model=1024,
    num_heads=16,
    num_kv_heads=16,
    head_dim=64,
    d_ff=2624,
    vocab_size=50368,
    act="geglu",
    norm="layernorm",
    norm_bias=False,
    norm_eps=1e-5,
    rope_theta=160000.0,
    local_rope_theta=10000.0,
    global_every=3,
    local_window=64,
    embed_norm=True,
    pool="mean",
    embed_dim=1024,
    max_tokens=8192,
    # documents are indexed in context-length windows, so the tiers are
    # calibrated at the length they serve
    probe_tokens=8192,
    source="arXiv:2412.13663 (ModernBERT); answerdotai/ModernBERT-large",
)
