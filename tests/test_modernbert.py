"""ModernBERT-large on the normal serving path, at a small size on the CPU
with seeded weights: banded-local and global layers in their published
order, a rotary theta each, GeGLU, the embedding norm, mean pooling.

The float32 reference is the benchmark's plain one
(``bench/arch/modernbert.py``), which imports nothing of the program; the
tests hand it the program's own weights.
"""
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.routing import CPU, NPU, Query
from repro.core.sharded_backend import ShardedEmbedderBackend
from repro.launch import serve
from repro.models import embedder
from repro.models import layers as L

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.arch import modernbert as reference  # noqa: E402

SEED = 11
# float32 against float32: the program's chunked attention and fused
# reductions sum in another order than the reference, about 1e-7 apart at
# this width; bf16 serving reads about 3e-3
FP32_TOL = 1e-5


def small_config():
    """Every published mechanism at a CPU size: layer 0 and one period
    (local, local, global), a band of |i - j| <= 8, MHA."""
    return dataclasses.replace(
        get_config("modernbert-large"), name="modernbert-small",
        num_layers=4, d_model=128, num_heads=4, num_kv_heads=4, head_dim=32,
        d_ff=256, vocab_size=512, local_window=8, max_tokens=64,
        probe_tokens=64)


def reference_cfg(cfg):
    """The configuration file's keys, as the reference reads them."""
    return {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.num_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "norm_eps": cfg.norm_eps, "pooling": cfg.pool,
            "global_attn_every_n_layers": cfg.global_every,
            "local_attention": 2 * cfg.local_window,
            "global_rope_theta": cfg.rope_theta,
            "local_rope_theta": cfg.local_rope_theta}


def reference_weights(params):
    blocks = params["blocks"]
    return {"embed": params["embed"],
            "layers": {**{k: blocks["attn"][k] for k in ("wq", "wk", "wv",
                                                          "wo")},
                       "w_in": blocks["ffn"]["w_in"],
                       "w_out": blocks["ffn"]["w_out"]}}


def reference_embed(cfg, params, payloads):
    S = max(len(p) for p in payloads)
    toks = np.zeros((len(payloads), S), np.int32)
    mask = np.zeros((len(payloads), S), np.float32)
    for i, p in enumerate(payloads):
        toks[i, :len(p)], mask[i, :len(p)] = p, 1.0
    fwd = reference.forward(reference_cfg(cfg))
    with jax.default_matmul_precision("highest"):
        return np.asarray(fwd(reference_weights(params), jnp.asarray(toks),
                              jnp.asarray(mask)))


@pytest.fixture(scope="module")
def small():
    cfg = small_config()
    return cfg, embedder.init_embedder(jax.random.PRNGKey(SEED), cfg)


def payloads(cfg, lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_served_path_agrees_with_the_float32_reference(small, monkeypatch,
                                                       dtype):
    """``build_engine`` through ``WindVE.submit``: float32 serving agrees
    with the reference, bf16 serving misses the float32 tolerance."""
    from repro import perf_flags

    cfg, params = small
    monkeypatch.setattr(serve, "get_config", lambda name: cfg)
    monkeypatch.setattr(embedder, "init_embedder", lambda key, c: params)
    # a fixed depth: an Eq. 12 sweep timed on a loaded test host can refuse
    # its own fit, which says nothing about the model
    monkeypatch.setattr(serve, "calibrate",
                        lambda name, profile, slo, points, **kw:
                        (64, serve.fit_latency([1, 64], [0.01, slo])))
    monkeypatch.setattr(perf_flags, "FLAGS",
                        dataclasses.replace(perf_flags.FLAGS,
                                            embed_dtype=dtype))
    engine, _ = serve.build_engine("modernbert-large", slo=1.0)
    qs = payloads(cfg, [5, 17, 40, 64, 23, 9])
    try:
        futs = [engine.submit(payload=p, length=len(p)) for p in qs]
        got = np.stack([f.result(timeout=60) for f in futs])
    finally:
        engine.shutdown()
    gap = np.linalg.norm(got - reference_embed(cfg, params, qs), axis=-1)
    if dtype == "fp32":
        assert gap.max() < FP32_TOL
    else:
        assert gap.max() > 10 * FP32_TOL


def test_answers_are_invariant_to_padding_across_buckets(small):
    cfg, params = small
    be = ShardedEmbedderBackend(cfg, params, max_tokens=cfg.max_tokens,
                                dtype="fp32", min_seq_bucket=16)
    short, long = payloads(cfg, [11, 60])
    alone = be.embed_batch([Query(qid=0, payload=short, length=11)])[0]
    padded = be.embed_batch([Query(qid=0, payload=short, length=11),
                             Query(qid=1, payload=long, length=60)])[0]
    assert {s for _, s in be.warm_buckets} == {16, 64}
    np.testing.assert_allclose(alone, padded, atol=1e-6)


def test_a_local_layer_ignores_tokens_outside_its_band(small):
    cfg, params = small
    attn = jax.tree.map(lambda x: x[1], params["blocks"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 48, cfg.d_model))
    pos = jnp.arange(48, dtype=jnp.int32)
    far = x.at[0, 30].add(3.0)           # 20 positions from query 10

    def run(h, window):
        return L.attn_forward(attn, cfg, h, pos, causal=False, window=window,
                              theta=cfg.local_rope_theta)

    local, local_far = run(x, cfg.local_window), run(far, cfg.local_window)
    w = cfg.local_window
    np.testing.assert_array_equal(local[0, :30 - w], local_far[0, :30 - w])
    assert np.abs(local[0, 30 - w] - local_far[0, 30 - w]).max() > 1e-3
    glob, glob_far = run(x, 0), run(far, 0)
    assert np.abs(glob[0, 10] - glob_far[0, 10]).max() > 1e-3


def test_layers_0_3_and_on_are_the_global_ones():
    cfg = get_config("modernbert-large")
    glob = [i for i in range(cfg.num_layers) if cfg.is_global_layer(i)]
    assert glob == list(range(0, 28, 3)) and len(glob) == 10
    # keys the real tokens attend: every one on 10 layers, the band of
    # |i - j| <= 64 on 18
    n = 8192
    band = sum(min(n - 1, i + 64) - max(0, i - 64) + 1 for i in range(n))
    assert embedder.attended_keys(cfg, [n]) == {"global": 10 * n * n,
                                                "local": 18 * band}
    assert embedder.attended_keys(get_config("bge-large-zh-v1.5"),
                                  [75]) == {"global": 24 * 75 * 75}


def test_the_stack_runs_each_kind_under_its_scope(small):
    cfg, params = small
    toks = jnp.ones((1, 40), jnp.int32)
    hlo = jax.jit(lambda p, t: embedder.embed(p, cfg, t)).lower(
        params, toks).as_text(debug_info=True)
    assert "attn_local" in hlo and "attn_global" in hlo


def test_a_local_layer_compiles_to_its_band_not_s_squared():
    """The compiled cost of one local layer's attention at 8,192 tokens is
    under twice the operations of its band, far below S^2."""
    cfg = get_config("modernbert-large")
    H, hd, S, w = cfg.num_heads, cfg.head_dim, 8192, cfg.local_window
    qkv = [jax.ShapeDtypeStruct((1, S, H, hd), jnp.bfloat16)] * 3
    mask = jax.ShapeDtypeStruct((1, S), jnp.float32)

    def attend(q, k, v, m):
        from repro.kernels.flash_attention import flash_attention_tokens

        n = jnp.sum(m != 0, axis=-1).astype(jnp.int32)
        return flash_attention_tokens(q, k, v, causal=False, window=w,
                                      kv_len=n)

    cost = jax.jit(attend).lower(*qkv, mask).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    band = 4 * H * hd * embedder.band_pairs(S, w)
    assert band < cost["flops"] < 2 * band
    assert cost["flops"] < 4 * H * hd * S * S / 20


def _uniform_stack_embed(params, cfg, tokens, mask, compute_dtype):
    """The embed step as it ran before stacks had two kinds of block: one
    scan over identical pre-LN blocks, sinusoidal positions."""
    from repro.kernels.pool_norm import pool_norm

    S = tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    h = params["embed"][tokens].astype(compute_dtype)
    h = h + L.sinusoidal_positions(positions, cfg.d_model).astype(h.dtype)

    def body(h, bp):
        hin = L.apply_norm(bp["norm1"], cfg, h)
        h = h + L.attn_forward(bp["attn"], cfg, hin, positions, causal=False,
                               kv_mask=mask)
        hin = L.apply_norm(bp["norm2"], cfg, h)
        return h + L.apply_mlp(bp["ffn"], cfg, hin), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = L.apply_norm(params["final_norm"], cfg, h)
    return pool_norm(h, mask, pool="cls")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bge_golden_path_is_bit_identical(dtype):
    """bge's golden parameters through the embed step give, bit for bit,
    what the single-kind stack gave."""
    import test_golden_embeddings as golden

    data = np.load(golden.GOLDEN)
    params: dict = {}
    for key in data.files:
        if key.startswith("param:"):
            node, parts = params, key[len("param:"):].split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(data[key])
    cfg = golden.golden_config()
    qs = [data[f"query:{i}"] for i in range(8)]
    S = max(len(q) for q in qs)
    toks = np.zeros((8, S), np.int32)
    mask = np.zeros((8, S), np.float32)
    for i, q in enumerate(qs):
        toks[i, :len(q)], mask[i, :len(q)] = q, 1.0
    args = (params, cfg, jnp.asarray(toks), jnp.asarray(mask))
    now = jax.jit(lambda p, t, m: embedder.embed(
        p, cfg, t, m, compute_dtype=dtype))(*args[:1], *args[2:])
    before = jax.jit(lambda p, t, m: _uniform_stack_embed(
        p, cfg, t, m, dtype))(*args[:1], *args[2:])
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))


# ------------------------------------------------ the offload tier's check --
class TokenTimedBackend:
    """A stand-in backend whose service time grows with the tokens served:
    the host CPU's, steep enough that long inputs miss the SLO."""

    def __init__(self, cfg, params, *, max_tokens, devices, min_seq_bucket):
        self.devices = list(devices)
        self.platform = self.devices[0].platform
        self.device_count = 1
        self.min_batch_bucket = 1
        self.dtype = "fp32"
        self.name = f"timed@{self.platform}"
        self.traces = 0
        self.served = []
        self.per_token_s = 2.5e-4 if self.platform == "cpu" else 1e-6

    def warm_grid(self, max_batch):
        return []

    def prewarm(self, grid):
        return 0

    def embed_batch(self, queries):
        n = sum(len(q.payload) for q in queries)
        self.served.append((len(queries), max(len(q.payload)
                                              for q in queries)))
        time.sleep(2e-3 + self.per_token_s * n)
        return [np.full(4, 0.5, np.float32) for _ in queries]


@dataclasses.dataclass(frozen=True)
class FakeTPU:
    id: int = 0
    platform: str = "tpu"
    device_kind: str = "TPU v5 lite"


def test_an_offload_tier_whose_smallest_batch_misses_the_slo_gets_depth_0():
    """Timed once at 128 tokens, then 256: the second probe misses the SLO,
    so the depth is 0 after it and no sweep runs."""
    probed = []

    def profile(c, length=1024, passes=2):
        probed.append((c, length, passes))
        return 0.15 * c * length / 512

    check = serve.slo_check(profile, 0.25, 1024)
    assert serve.calibrate(CPU, profile, 0.25, (2, 4, 8, 16),
                           check=check) == (0, None)
    assert probed == [(2, 128, 1), (2, 256, 1), (2, 512, 1)]

    # at its calibration length alone where that is the shorter, and a
    # tier that meets the SLO goes on to its sweep
    def fast(c, length=75, passes=2):
        probed.append((c, length, passes))
        return 0.002 + 0.01 * c

    probed.clear()
    depth, fit = serve.calibrate(CPU, fast, 0.25, (2, 4, 8, 16),
                                 check=serve.slo_check(fast, 0.25, 75))
    assert probed[:5] == [(2, 75, 1), (2, 75, 2), (4, 75, 2), (8, 75, 2),
                          (16, 75, 2)]
    assert depth == 24 and fit is not None


@pytest.mark.parametrize("model,offload", [("modernbert-large", False),
                                           ("bge-large-zh-v1.5", True)])
def test_the_engine_leaves_out_an_offload_tier_that_misses_the_slo(
        monkeypatch, capsys, model, offload):
    from repro.core.device_detector import DeviceInventory

    cpu = jax.devices("cpu")[0]
    monkeypatch.setattr(serve, "probe_jax_devices", lambda: DeviceInventory(
        npus=1, cpus=1, npu_devices=(FakeTPU(),), cpu_devices=(cpu,)))
    monkeypatch.setattr(serve, "ShardedEmbedderBackend", TokenTimedBackend)
    if not offload:
        # documents of 1,024 tokens: 2 x 512 take 0.26 s on the CPU
        monkeypatch.setattr(serve, "get_config",
                            lambda name: dataclasses.replace(
                                small_config(), max_tokens=1024,
                                probe_tokens=1024))
    engine, cfg = serve.build_engine(model, slo=0.2, smoke=offload)
    try:
        tiers = {t.name for t in engine.qm.tiers}
    finally:
        engine.shutdown()
    assert tiers == ({NPU, CPU} if offload else {NPU})
    out = capsys.readouterr().out
    assert ("C_CPU=0" in out) is not offload
