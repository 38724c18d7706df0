"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           flash_attention_pallas)
from repro.kernels.pool_norm import pool_norm, pool_norm_pallas, pool_norm_ref
from repro.kernels.quant_matmul import (quant_matmul, quant_matmul_pallas,
                                        quant_matmul_ref, quant_matmul_w8a8,
                                        quantize_activations,
                                        w8a8_matmul_pallas, w8a8_matmul_ref)
from repro.kernels.rmsnorm import rmsnorm_pallas, rmsnorm_ref
from repro.kernels.ssm_scan import ssm_scan_pallas, ssm_scan_ref

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------- flash ----
FLASH_CASES = [
    # B, H, KV, Sq, Sk, hd, causal, window
    (2, 4, 2, 256, 256, 64, True, 0),     # GQA causal, aligned
    (1, 4, 4, 128, 384, 64, False, 0),    # MHA cross-shaped, Sk > Sq
    (2, 8, 2, 200, 200, 128, True, 64),   # sliding window + padding
    (1, 2, 1, 96, 96, 32, True, 0),       # small head_dim, padding
    (1, 6, 3, 130, 257, 64, True, 0),     # both dims ragged
    (2, 4, 4, 300, 300, 64, False, 16),   # bidirectional band + padding
    (1, 16, 16, 384, 384, 64, False, 64),  # ModernBERT's band, 16 heads
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(case, dtype):
    B, H, KV, Sq, Sk, hd, causal, window = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, Sk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, Sk, hd), jnp.float32).astype(dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


RAGGED_CASES = [
    # B, H, KV, Sq, Sk, hd, causal, lens
    (2, 4, 2, 96, 96, 64, False, (50, 96)),     # embedder-shaped, ragged
    (2, 2, 1, 64, 64, 32, True, (10, 64)),      # causal + ragged
    (3, 4, 4, 130, 130, 64, False, (1, 77, 130)),  # block padding + ragged
]


@pytest.mark.parametrize("case", RAGGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kv_len_vs_ref(case, dtype):
    """Per-example valid-key prefixes (ragged/bucketed batches)."""
    B, H, KV, Sq, Sk, hd, causal, lens = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, Sk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, Sk, hd), jnp.float32).astype(dtype)
    kv_len = jnp.asarray(lens, jnp.int32)
    ref = attention_ref(q, k, v, causal=causal, kv_len=kv_len)
    got = flash_attention_pallas(q, k, v, causal=causal, interpret=True,
                                 kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))
    # the pure-JAX chunked path must mask identically (kv_len -> kv_mask)
    gj = flash_attention(q, k, v, causal=causal, backend="jnp",
                         kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(gj, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("window,blocks", [(16, (64, 64, 1)),
                                           (64, (128, 128, 8)),
                                           (0, (128, 256, 2))])
def test_token_major_kernel_and_band_paths_mask_ragged_rows(window, blocks):
    """The token-major kernel and the pure-JAX paths, with a symmetric band
    and per-example valid keys, agree with the oracle on every real row."""
    from repro.kernels.flash_attention import (flash_attention_tokens,
                                               flash_attention_tokens_pallas)

    B, S, H, hd = 2, 300, 4, 64
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(x, (B, S, H, hd)) for x in ks)
    n = jnp.asarray([300, 170], jnp.int32)
    t = lambda x: jnp.moveaxis(x, 1, 2)          # noqa: E731
    real = (jnp.arange(S)[None, :, None, None] < n[:, None, None, None])
    ref = jnp.where(real, t(attention_ref(t(q), t(k), t(v), causal=False,
                                          window=window, kv_len=n)), 0)
    bq, bk, bh = blocks
    got = flash_attention_tokens_pallas(q, k, v, causal=False, window=window,
                                        kv_len=n, interpret=True, block_q=bq,
                                        block_k=bk, block_h=bh)
    jnp_path = flash_attention_tokens(q, k, v, causal=False, window=window,
                                      kv_len=n, backend="jnp")
    for out in (got, jnp_path):
        np.testing.assert_allclose(np.asarray(jnp.where(real, out, 0)),
                                   np.asarray(ref), atol=2e-5)


def test_attn_forward_kernel_flag_matches_jnp_path():
    """FLAGS.attn_kernel routes attn_forward through the Pallas kernel; the
    interpreted kernel must agree with the default pure-JAX path, masks
    included (the embedder's serving configuration)."""
    from repro.configs import get_config
    from repro.models import layers as L
    from repro.perf_flags import reset_flags, set_flags
    cfg = get_config("bge-large-zh-v1.5").smoke()
    p = L.init_attention(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 40, cfg.d_model))
    pos = jnp.arange(40, dtype=jnp.int32)
    kv_mask = (jnp.arange(40)[None, :] <
               jnp.asarray([[23], [40]])).astype(jnp.float32)
    base = L.attn_forward(p, cfg, x, pos, causal=False, kv_mask=kv_mask)
    try:
        set_flags(attn_kernel="interpret")
        kernel = L.attn_forward(p, cfg, x, pos, causal=False,
                                kv_mask=kv_mask)
    finally:
        reset_flags()
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(base),
                               atol=2e-5)


def test_flash_jnp_backend_matches_ref():
    q = jax.random.normal(KEY, (2, 4, 160, 64))
    k = jax.random.normal(KEY, (2, 2, 160, 64))
    v = jax.random.normal(KEY, (2, 2, 160, 64))
    ref = attention_ref(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_block_size_invariance():
    q = jax.random.normal(KEY, (1, 2, 256, 64))
    k = jax.random.normal(KEY, (1, 2, 256, 64))
    v = jax.random.normal(KEY, (1, 2, 256, 64))
    a = flash_attention_pallas(q, k, v, block_q=64, block_k=64, interpret=True)
    b = flash_attention_pallas(q, k, v, block_q=128, block_k=256,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------- ssm ------
SSM_CASES = [
    # B, S, DI, N, chunk, block_di
    (2, 256, 512, 16, 128, 512),
    (1, 128, 1024, 16, 64, 256),
    (2, 64, 256, 8, 64, 256),
    (1, 64, 128, 16, 16, 128),
]


@pytest.mark.parametrize("case", SSM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_vs_ref(case, dtype):
    B, S, DI, N, chunk, bdi = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, DI), jnp.float32).astype(dtype)
    dt = (jax.nn.softplus(jax.random.normal(ks[1], (B, S, DI))) * 0.1
          ).astype(dtype)
    Bm = jax.random.normal(ks[2], (B, S, N), jnp.float32).astype(dtype)
    Cm = jax.random.normal(ks[3], (B, S, N), jnp.float32).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (DI, N)) * 0.5)
    yr, hr = ssm_scan_ref(x, dt, Bm, Cm, A)
    yp, hp = ssm_scan_pallas(x, dt, Bm, Cm, A, chunk=chunk, block_di=bdi,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                               atol=tol(dtype) * 10, rtol=tol(dtype) * 10)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr),
                               atol=tol(dtype) * 10, rtol=tol(dtype) * 10)


def test_ssm_chunking_invariance():
    B, S, DI, N = 1, 128, 256, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, DI))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, DI))) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    A = -jnp.exp(jax.random.normal(ks[4], (DI, N)) * 0.5)
    y1, h1 = ssm_scan_pallas(x, dt, Bm, Cm, A, chunk=32, interpret=True)
    y2, h2 = ssm_scan_pallas(x, dt, Bm, Cm, A, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)


def test_ssm_matches_model_layer_scan():
    """The model's mamba_scan_ref and the kernel ref must agree."""
    from repro.models.layers import mamba_scan_ref
    B, S, DI, N = 2, 64, 128, 16
    ks = jax.random.split(KEY, 5)
    xc = jax.random.normal(ks[0], (B, S, DI))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, DI))) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, N))
    Cm = jax.random.normal(ks[3], (B, S, N))
    A = -jnp.exp(jax.random.normal(ks[4], (DI, N)) * 0.5)
    y1, h1 = mamba_scan_ref(xc, dt, Bm, Cm, A)
    y2, h2 = ssm_scan_ref(xc, dt, Bm, Cm, A)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)


# ---------------------------------------------------------------- pool ----
POOL_CASES = [
    # B, S, D, block_b
    (2, 33, 128, 2),      # ragged rows + batch-block padding
    (5, 64, 256, 8),      # block_b > B
    (1, 16, 64, 1),
    (9, 40, 128, 4),      # B not a multiple of block_b
]


@pytest.mark.parametrize("case", POOL_CASES)
@pytest.mark.parametrize("pool", ["mean", "cls"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pool_norm_vs_ref(case, pool, dtype):
    B, S, D, bb = case
    ks = jax.random.split(KEY, 2)
    h = jax.random.normal(ks[0], (B, S, D), jnp.float32).astype(dtype)
    lens = jax.random.randint(ks[1], (B,), 1, S + 1)
    mask = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)
    ref = pool_norm_ref(h, mask, pool)
    got = pool_norm_pallas(h, mask, pool, block_b=bb, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=tol(dtype), rtol=tol(dtype))
    assert got.dtype == jnp.float32            # paper: fp32 output vectors
    norms = np.linalg.norm(np.asarray(got), axis=-1)
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)


def test_pool_norm_fully_masked_row_is_zero():
    """A bucketed batch's padding row (all-zero mask) pools to the zero
    vector in both modes — no NaNs, no garbage unit vectors."""
    h = jax.random.normal(KEY, (2, 8, 16))
    mask = jnp.zeros((2, 8)).at[0, :3].set(1.0)
    for pool in ("mean", "cls"):
        for fn in (pool_norm_ref,
                   lambda a, b, p: pool_norm_pallas(a, b, p, interpret=True)):
            out = np.asarray(fn(h, mask, pool))
            assert np.isfinite(out).all()
            assert np.linalg.norm(out[0]) == pytest.approx(1.0, abs=1e-5)
            assert np.abs(out[1]).max() == 0.0


def test_pool_norm_matches_embedder_tail():
    """The ops wrapper (backend dispatch) is what models.embedder calls; its
    'ref' route must equal the kernel route."""
    h = jax.random.normal(KEY, (3, 24, 64))
    mask = (jnp.arange(24)[None, :] <
            jnp.asarray([[24], [10], [1]])).astype(jnp.float32)
    a = pool_norm(h, mask, pool="mean", backend="ref")
    b = pool_norm(h, mask, pool="mean", backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_pool_norm_rejects_unknown_mode():
    h = jnp.zeros((1, 4, 8))
    m = jnp.ones((1, 4))
    with pytest.raises(ValueError):
        pool_norm_ref(h, m, "max")
    with pytest.raises(ValueError):
        pool_norm_pallas(h, m, "max", interpret=True)


# ---------------------------------------------------------------- quant ----
QM_CASES = [
    # M, K, N, block_m, block_n, block_k
    (128, 128, 128, 128, 128, 128),   # exactly one block
    (200, 96, 260, 128, 128, 64),     # every dim ragged vs its block
    (7, 48, 130, 8, 128, 32),         # small M, K split across steps
    (256, 320, 64, 64, 64, 128),      # multi-block M and K
    (1, 16, 24, 128, 128, 128),       # single row, tiny dims
]


@pytest.mark.parametrize("case", QM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_vs_ref(case, dtype):
    """Pallas (interpret) fused int8 matmul == the jnp oracle across block
    raggedness and both activation dtypes (fp32 accumulation in both)."""
    M, K, N, bm, bn, bk = case
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (M, K), jnp.float32).astype(dtype)
    w8 = jax.random.randint(ks[1], (K, N), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    scale = jnp.abs(jax.random.normal(KEY, (N,))) * 0.01 + 1e-4
    ref = quant_matmul_ref(x, w8, scale)
    got = quant_matmul_pallas(x, w8, scale, block_m=bm, block_n=bn,
                              block_k=bk, interpret=True)
    assert got.dtype == ref.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_quant_matmul_leading_batch_dims():
    x = jax.random.normal(KEY, (2, 9, 48))
    w8 = jax.random.randint(KEY, (48, 64), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    s = jnp.full((64,), 0.02)
    a = quant_matmul_ref(x, w8, s)
    b = quant_matmul_pallas(x, w8, s, interpret=True)
    assert a.shape == b.shape == (2, 9, 64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_quant_matmul_ops_backend_dispatch():
    """The jit ops wrapper: 'ref' and 'interpret' routes agree; int8 weights
    are mandatory (a float weight means the caller forgot to quantize)."""
    x = jax.random.normal(KEY, (5, 32))
    w8 = jax.random.randint(KEY, (32, 40), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    s = jnp.full((40,), 0.03)
    a = quant_matmul(x, w8, s, backend="ref")
    b = quant_matmul(x, w8, s, backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    with pytest.raises(TypeError, match="int8"):
        quant_matmul_ref(x, x, s)
    with pytest.raises(TypeError, match="int8"):
        quant_matmul_pallas(x, x.astype(jnp.float32), s, interpret=True)


def test_quant_matmul_block_size_invariance():
    x = jax.random.normal(KEY, (96, 160))
    w8 = jax.random.randint(KEY, (160, 192), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    s = jnp.abs(jax.random.normal(KEY, (192,))) * 0.01 + 1e-4
    a = quant_matmul_pallas(x, w8, s, block_m=32, block_n=64, block_k=32,
                            interpret=True)
    b = quant_matmul_pallas(x, w8, s, block_m=96, block_n=192, block_k=160,
                            interpret=True)
    # K-split changes fp32 accumulation order only
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                               rtol=1e-5)


def test_quant_matmul_matches_dense_apply_contract():
    """quantize_dense + quant_matmul approximates the float projection the
    way models.layers.dense_apply relies on (error bounded by the
    per-channel scale)."""
    from repro.models.quantize import quantize_dense
    w = jax.random.normal(KEY, (64, 96)) * jnp.linspace(0.2, 2.0, 96)
    q, s = quantize_dense(w)
    x = jax.random.normal(KEY, (8, 64))
    got = np.asarray(quant_matmul_pallas(x, q, s, interpret=True))
    want = np.asarray(x @ w)
    # |err| <= sum_k |x_k| * scale_n / 2 elementwise
    bound = (np.abs(np.asarray(x)).sum(-1, keepdims=True)
             * np.asarray(s)[None, :] * 0.5 + 1e-5)
    assert (np.abs(got - want) <= bound).all()


# ---------------------------------------------------------------- w8a8 -----
W8A8_CASES = [
    # M, K, N, block_m, block_n, block_k
    (128, 128, 128, 128, 128, 128),   # exactly one block
    (200, 96, 260, 128, 128, 64),     # every dim ragged vs its block
    (7, 48, 130, 8, 128, 32),         # small M, K split across steps
    (256, 320, 64, 64, 64, 128),      # multi-block M and K
    (1, 16, 24, 128, 128, 128),       # single row, tiny dims
    (33, 512, 48, 16, 32, 128),       # deep K: int16 accumulation would clip
]


def _np_w8a8_oracle(x8, w8, xs, ws):
    """Exact numpy int32-accumulation oracle (int64 overflow check)."""
    acc64 = np.asarray(x8, np.int64) @ np.asarray(w8, np.int64)
    assert np.abs(acc64).max() < 2 ** 31, "oracle itself would overflow"
    acc = acc64.astype(np.int32)
    return (acc.astype(np.float32) * np.asarray(xs, np.float32)[:, None]
            * np.asarray(ws, np.float32)[None, :])


@pytest.mark.parametrize("case", W8A8_CASES)
def test_w8a8_matmul_vs_int32_oracle(case):
    """Pallas (interpret) and jnp W8A8 routes == the exact numpy int32
    oracle across block raggedness.  The contraction is integer, so the
    match is exact up to the final fp32 dequant rounding."""
    M, K, N, bm, bn, bk = case
    ks = jax.random.split(KEY, 4)
    x8 = jax.random.randint(ks[0], (M, K), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    w8 = jax.random.randint(ks[1], (K, N), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    xs = jnp.abs(jax.random.normal(ks[2], (M,))) * 0.02 + 1e-4
    ws = jnp.abs(jax.random.normal(ks[3], (N,))) * 0.01 + 1e-4
    want = _np_w8a8_oracle(x8, w8, xs, ws)
    got_p = w8a8_matmul_pallas(x8, w8, xs, ws, block_m=bm, block_n=bn,
                               block_k=bk, interpret=True)
    got_r = w8a8_matmul_ref(x8, w8, xs, ws)
    assert got_p.dtype == got_r.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_p), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_r), want, rtol=1e-6)


def test_w8a8_accumulates_in_int32_not_float():
    """Constructed so a float32 running accumulator would round: all-positive
    int8 operands drive the partial sums past 2^24 (fp32 integer-exactness
    limit) with odd per-tile increments, while the exact int32 sum converted
    ONCE to fp32 is what both routes must return bit-exactly."""
    rng = np.random.default_rng(0)
    M, K, N = 2, 6144, 8
    x8 = jnp.asarray(rng.integers(1, 128, (M, K)).astype(np.int8))
    w8 = jnp.asarray(rng.integers(1, 128, (K, N)).astype(np.int8))
    ones_m, ones_n = jnp.ones((M,)), jnp.ones((N,))
    acc64 = np.asarray(x8, np.int64) @ np.asarray(w8, np.int64)
    assert acc64.max() > 2 ** 24, "case must exceed fp32 exact-int range"
    assert acc64.max() < 2 ** 31
    want = acc64.astype(np.int32).astype(np.float32)   # single final rounding
    got_p = w8a8_matmul_pallas(x8, w8, ones_m, ones_n, block_m=8,
                               block_n=8, block_k=64, interpret=True)
    got_r = w8a8_matmul_ref(x8, w8, ones_m, ones_n)
    np.testing.assert_array_equal(np.asarray(got_p), want)
    np.testing.assert_array_equal(np.asarray(got_r), want)


def test_quantize_activations_extreme_ranges():
    """absmax≈0 rows must not NaN (guarded scale divide), subnormal rows
    must not overflow the int8 clip, huge rows stay finite."""
    K = 64
    x = jnp.stack([
        jnp.zeros((K,)),                                  # exactly zero
        jnp.full((K,), 1e-42),                            # subnormal absmax
        jnp.full((K,), 1e30),                             # huge
        jnp.linspace(-3.0, 3.0, K),                       # ordinary
        jnp.zeros((K,)).at[0].set(1e-45),                 # one denormal elt
    ])
    x8, scale = quantize_activations(x)
    assert x8.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert bool(jnp.isfinite(scale).all())
    assert bool((scale > 0).all())
    assert int(jnp.abs(x8).max()) <= 127
    assert int(jnp.abs(x8[0]).max()) == 0                 # zero row -> zeros
    # dequant round-trips ordinary rows within scale/2 per element
    err = jnp.abs(x8[3].astype(jnp.float32) * scale[3] - x[3])
    assert float(err.max()) <= float(scale[3]) * 0.5 + 1e-7
    # end-to-end: extreme rows stay finite through the kernel
    w8 = jax.random.randint(KEY, (K, 16), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    ws = jnp.full((16,), 0.01)
    out = quant_matmul_w8a8(x, w8, ws)
    assert bool(jnp.isfinite(out).all())
    assert bool((out[0] == 0).all())


def test_w8a8_matmul_leading_batch_dims():
    x = jax.random.normal(KEY, (2, 9, 48))
    w8 = jax.random.randint(KEY, (48, 64), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    s = jnp.full((64,), 0.02)
    out = quant_matmul_w8a8(x, w8, s)
    assert out.shape == (2, 9, 64) and out.dtype == x.dtype
    x8, xs = quantize_activations(x)
    # fp32 dequant-epilogue fusion order may differ under jit: atol covers
    # the last-ulp wobble, the integer contraction itself is exact
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(w8a8_matmul_ref(x8, w8, xs, s)),
        atol=1e-4)


def test_w8a8_rejects_unquantized_operands():
    x8 = jnp.zeros((4, 32), jnp.int8)
    xf = jnp.zeros((4, 32), jnp.float32)
    w8 = jnp.zeros((32, 16), jnp.int8)
    s = jnp.ones((16,))
    xs = jnp.ones((4,))
    with pytest.raises(TypeError, match="int8"):
        w8a8_matmul_ref(xf, w8, xs, s)
    with pytest.raises(TypeError, match="int8"):
        w8a8_matmul_pallas(x8, xf.T, xs, s, interpret=True)
    with pytest.raises(TypeError, match="int8"):
        w8a8_matmul_pallas(xf, w8, xs, s, interpret=True)


def test_w8a8_block_size_invariance():
    """Integer accumulation makes the K-split bitwise irrelevant (unlike
    the fp32-accumulating weight-only kernel, which only matches to
    rounding): any block tiling returns the identical result."""
    ks = jax.random.split(KEY, 2)
    x8 = jax.random.randint(ks[0], (96, 160), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    w8 = jax.random.randint(ks[1], (160, 192), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    xs = jnp.abs(jax.random.normal(KEY, (96,))) * 0.02 + 1e-4
    ws = jnp.abs(jax.random.normal(KEY, (192,))) * 0.01 + 1e-4
    a = w8a8_matmul_pallas(x8, w8, xs, ws, block_m=32, block_n=64,
                           block_k=32, interpret=True)
    b = w8a8_matmul_pallas(x8, w8, xs, ws, block_m=96, block_n=192,
                           block_k=160, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- quant dispatch ----------
def test_quant_interpret_default_resolves_from_backend():
    """Satellite: quant_matmul_pallas / w8a8_matmul_pallas never default to
    the interpreter — a caller gets the compiled kernel unless it asks for
    ``interpret=True``, whatever the process's default backend is."""
    import importlib
    import inspect

    # the package re-exports the jitted entry under the same name, so the
    # kernel MODULE must be resolved explicitly
    kmod = importlib.import_module("repro.kernels.quant_matmul.quant_matmul")

    for fn in (kmod.quant_matmul_pallas, kmod.w8a8_matmul_pallas):
        assert inspect.signature(fn).parameters["interpret"].default is False
    assert not hasattr(kmod, "_default_interpret")


def _lowered_for(fn, platform, *args):
    """StableHLO text of ``fn`` lowered for ``platform`` (no device needed:
    this steers placement the way a jit on that platform's devices does)."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()


def test_quant_ops_auto_routes_pallas_compiled_on_tpu():
    """The ops auto route follows placement: lowered for a TPU it holds the
    compiled Mosaic kernel (a ``tpu_custom_call`` — interpret mode would
    lower to plain HLO loops), lowered for the CPU it holds no kernel and
    computes the ref oracle."""
    from repro.kernels.quant_matmul import ops as qm_ops

    x = jax.random.normal(KEY, (4, 32))
    w8 = jax.random.randint(KEY, (32, 16), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    s = jnp.full((16,), 0.02)
    for fn in (qm_ops._quant_matmul, qm_ops._quant_matmul_w8a8):
        assert "tpu_custom_call" in _lowered_for(fn, "tpu", x, w8, s)
        assert "tpu_custom_call" not in _lowered_for(fn, "cpu", x, w8, s)
    np.testing.assert_allclose(
        np.asarray(jax.jit(qm_ops._quant_matmul)(x, w8, s)),
        np.asarray(quant_matmul_ref(x, w8, s)), atol=1e-6)
    x8, xs = quantize_activations(x)
    np.testing.assert_allclose(
        np.asarray(jax.jit(qm_ops._quant_matmul_w8a8)(x, w8, s)),
        np.asarray(w8a8_matmul_ref(x8, w8, xs, s)), atol=1e-6)


def test_w8a8_ops_backend_dispatch():
    """The jit ops wrapper: 'ref' and 'interpret' routes agree bitwise
    (integer accumulation on both)."""
    x = jax.random.normal(KEY, (5, 32))
    w8 = jax.random.randint(KEY, (32, 40), -127, 128, jnp.int32
                            ).astype(jnp.int8)
    s = jnp.full((40,), 0.03)
    a = quant_matmul_w8a8(x, w8, s, backend="ref")
    b = quant_matmul_w8a8(x, w8, s, backend="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------- rmsnorm --
@pytest.mark.parametrize("shape", [(4, 128, 512), (2, 100, 384), (300, 256),
                                   (1, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_vs_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, jnp.float32).astype(dtype)
    s = jax.random.normal(KEY, (shape[-1],), jnp.float32)
    ref = rmsnorm_ref(x, s)
    got = rmsnorm_pallas(x, s, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_rmsnorm_matches_model_layer():
    from repro.configs import get_config
    from repro.models.layers import apply_norm
    cfg = get_config("stablelm-1.6b").smoke()
    x = jax.random.normal(KEY, (2, 8, cfg.d_model))
    scale = jnp.ones((cfg.d_model,)) * 1.3
    a = apply_norm({"scale": scale}, cfg, x)
    b = rmsnorm_ref(x, scale, eps=cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------- decode ---
from repro.kernels.flash_decode import decode_attention_ref, flash_decode_pallas

FD_CASES = [
    # B, KV, G, S, hd, pos, window, block_k
    (2, 2, 4, 512, 64, 300, 0, 256),      # partial-filled cache
    (1, 4, 2, 384, 128, 383, 0, 128),     # full cache, ragged blocks
    (2, 1, 8, 256, 64, 200, 64, 256),     # sliding window
    (1, 2, 1, 100, 32, 50, 0, 64),        # padding + small dims
]


@pytest.mark.parametrize("case", FD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_vs_ref(case, dtype):
    B, KV, G, S, hd, pos, window, bk = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32).astype(dtype)
    kpos = jnp.where(jnp.arange(S) <= pos, jnp.arange(S), -1)
    ref = decode_attention_ref(q, k, v, kpos, pos, window=window)
    got = flash_decode_pallas(q, k, v, kpos, pos, window=window, block_k=bk,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol(dtype) * 2, rtol=tol(dtype) * 2)


def test_flash_decode_ring_buffer_positions():
    """Slots hold non-monotonic absolute positions (sliding-window ring)."""
    B, KV, G, S, hd, W = 1, 2, 2, 128, 64, 128
    pos = 200                      # wrapped: slot i holds pos (200-127..200)
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    base = jnp.arange(S)
    kpos = jnp.where(base <= pos % S, base + (pos // S) * S,
                     base + (pos // S - 1) * S)
    ref = decode_attention_ref(q, k, v, kpos, pos, window=W)
    got = flash_decode_pallas(q, k, v, kpos, pos, window=W, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_flash_decode_matches_model_attn_decode_read():
    """Kernel == the serving path's attention math (layers.attn_decode_read
    modulo the wo projection)."""
    from repro.configs import get_config
    from repro.models import layers as L
    cfg = get_config("stablelm-1.6b").smoke()
    hd = cfg.resolved_head_dim
    B, S = 2, 64
    ks = jax.random.split(KEY, 4)
    p = L.init_attention(ks[0], cfg, jnp.float32)
    x1 = jax.random.normal(ks[1], (B, 1, cfg.d_model))
    ck = jax.random.normal(ks[2], (B, S, cfg.num_kv_heads, hd))
    cv = jax.random.normal(ks[3], (B, S, cfg.num_kv_heads, hd))
    pos = jnp.asarray(40)
    kpos = jnp.where(jnp.arange(S) <= pos, jnp.arange(S), -1)
    want = L.attn_decode_read(p, cfg, x1, pos, ck, cv, kpos)
    q = L.project_q(p, cfg, x1, pos).reshape(B, cfg.num_kv_heads, -1, hd)
    out = flash_decode_pallas(q, ck, cv, kpos, pos, interpret=True)
    got = out.reshape(B, 1, -1) @ p["wo"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
