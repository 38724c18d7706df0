"""Optimized-path correctness: every §Perf flag must be numerically
equivalent to the baseline path (fp32; bf16 MoE routing ties excepted —
see EXPERIMENTS.md §Perf notes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import perf_flags
from repro.configs import get_config
from repro.models import api, layers as L, lm


@pytest.fixture(autouse=True)
def _reset():
    perf_flags.reset_flags()
    yield
    perf_flags.reset_flags()


KEY = jax.random.PRNGKey(0)


def _fwd(arch, **flags):
    cfg = get_config(arch).smoke()
    if cfg.is_moe:
        cfg = cfg.replace(capacity_factor=float(cfg.num_experts))
    params = api.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 48), 0, cfg.vocab_size)
    base, _ = lm.forward(params, cfg, toks)
    perf_flags.set_flags(**flags)
    opt, _ = lm.forward(params, cfg, toks)
    perf_flags.reset_flags()
    return float(jnp.abs(base.astype(jnp.float32) -
                         opt.astype(jnp.float32)).max())


def test_attn_band_skip_exact():
    assert _fwd("stablelm-1.6b", attn_band_skip=True) == 0.0
    assert _fwd("starcoder2-7b", attn_band_skip=True) == 0.0   # window
    assert _fwd("hymba-1.5b", attn_band_skip=True) == 0.0


def test_mamba_chunked_scan_exact():
    assert _fwd("falcon-mamba-7b", mamba_chunk=16) == 0.0
    assert _fwd("hymba-1.5b", mamba_chunk=32) == 0.0


def test_moe_row_dispatch_fp32_exact():
    """fp32 single layer: row dispatch == global dispatch == per-token ref."""
    cfg = get_config("qwen3-moe-30b-a3b").smoke().replace(capacity_factor=4.0)
    p = L.init_moe(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (4, 32, cfg.d_model))
    ya, aux_a = L.apply_moe(p, cfg, x)
    perf_flags.set_flags(moe_row_dispatch=True)
    yb, aux_b = L.apply_moe(p, cfg, x)
    perf_flags.reset_flags()
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb), atol=1e-6)
    assert float(aux_a) == pytest.approx(float(aux_b), abs=1e-6)


def test_decode_fori_exact():
    for arch in ("stablelm-1.6b", "hymba-1.5b", "starcoder2-7b"):
        cfg = get_config(arch).smoke()
        params = api.init_params(KEY, cfg)
        toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
        _, cache = lm.prefill(params, cfg, toks, max_len=24,
                              cache_dtype=jnp.float32)
        nxt = jnp.array([1, 2], dtype=jnp.int32)
        lg1, c1 = lm.decode_step(params, cfg, nxt, cache)
        perf_flags.set_flags(decode_fori=True)
        lg2, c2 = lm.decode_step(params, cfg, nxt, cache)
        perf_flags.reset_flags()
        assert float(jnp.abs(lg1.astype(jnp.float32) -
                             lg2.astype(jnp.float32)).max()) == 0.0
        for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_decode_shard_map_single_device_mesh():
    """Flash-decode path on the host mesh (1x1 shards = trivial combine)."""
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.steps.serve import build_decode_step

    mesh = make_host_mesh()
    cfg = get_config("stablelm-1.6b").smoke()
    params = api.init_params(KEY, cfg, jnp.float32)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    _, cache = lm.prefill(params, cfg, toks, max_len=32,
                          cache_dtype=jnp.float32)
    nxt = jnp.array([1, 2], dtype=jnp.int32)
    shape = ShapeConfig("t", 32, 2, "decode")
    from repro.launch.mesh import mesh_context
    with mesh_context(mesh):
        step0 = build_decode_step(cfg, shape, mesh)
        t0, c0 = jax.jit(step0)(params, cache, {"token": nxt})
        perf_flags.set_flags(decode_shard_map=True)
        step1 = build_decode_step(cfg, shape, mesh)
        t1, c1 = jax.jit(step1)(params, cache, {"token": nxt})
        perf_flags.reset_flags()
    assert bool((t0 == t1).all())
    np.testing.assert_allclose(np.asarray(c0["k"]), np.asarray(c1["k"]),
                               atol=1e-6)


def test_serve_tp_only_specs_drop_data_axis():
    from repro.parallel import sharding
    from tests.test_sharding import MESH_1POD

    cfg = get_config("qwen2-72b")
    ps = jax.eval_shape(lambda: api.init_params(KEY, cfg, jnp.bfloat16))
    train_specs = sharding.param_pspecs(MESH_1POD, ps)
    serve_specs = sharding.param_pspecs(MESH_1POD, ps, mode="serve")
    assert train_specs["blocks"]["attn"]["wq"][1] == "data"
    assert serve_specs["blocks"]["attn"]["wq"][1] is None
    assert serve_specs["blocks"]["attn"]["wq"][2] == "model"


def test_parse_opt_roundtrip():
    kw = perf_flags.parse_opt("mamba_chunk=32,attn_band_skip=1,"
                              "remat_policy=dots,serve_tp_only=0")
    assert kw == {"mamba_chunk": 32, "attn_band_skip": True,
                  "remat_policy": "dots", "serve_tp_only": False}


def test_parse_opt_embed_serving_flags():
    kw = perf_flags.parse_opt("embed_dtype=bf16,embed_donate=1")
    assert kw == {"embed_dtype": "bf16", "embed_donate": True}
    # the drain follows the tier's platform, not a flag
    with pytest.raises(ValueError, match="embed_async"):
        perf_flags.parse_opt("embed_async=0")
    flags = perf_flags.set_flags(**kw)
    assert flags.embed_dtype == "bf16" and flags.embed_donate
    perf_flags.reset_flags()
    assert perf_flags.FLAGS.embed_dtype == "fp32"   # baseline oracle


def test_parse_opt_unknown_flag_lists_valid_names():
    with pytest.raises(ValueError) as ei:
        perf_flags.parse_opt("mamba_chunk=16,no_such_flag=1")
    msg = str(ei.value)
    assert "no_such_flag" in msg
    assert "mamba_chunk" in msg and "embed_dtype" in msg  # lists valid flags
