"""Compile the serving kernels, and whole embed steps, for a TPU v5e.

Nothing here runs on a chip: the TPU compiler is installed, so each test
compiles for a chip that is described and not attached, and fails where the
chip's compiler would refuse the program (tile alignment, VMEM, a kernel
that cannot be partitioned).  Interpret-mode tests (``test_kernels``) cannot
see those refusals.  The topology is described inside a fixture, never at
import, so every pytest worker collects the same tests and only the worker
given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.pool_norm import pool_norm
from repro.kernels.quant_matmul import (quant_matmul, quant_matmul_w8a8,
                                        w8a8_matmul_pallas)
from repro.models import embedder
from repro.models.quantize import serve_params, wants_act_quant


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    cache_on = jax.config.jax_enable_compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("shape,dtype", [
    ((8, 96, 1024), jnp.float32),
    ((8, 96, 1024), jnp.bfloat16),
    ((8, 512, 1024), jnp.float32),
])
@pytest.mark.parametrize("pool", ["cls", "mean"])
def test_pool_norm_compiles_for_v5e(one_chip, shape, dtype, pool):
    B, S, D = shape
    hlo = _compile(lambda h, m: pool_norm(h, m, pool=pool),
                   _spec(shape, dtype, one_chip),
                   _spec((B, S), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_compiles_for_v5e(one_chip, dtype):
    M, K, N = 768, 1024, 4096
    hlo = _compile(quant_matmul, _spec((M, K), dtype, one_chip),
                   _spec((K, N), jnp.int8, one_chip),
                   _spec((N,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


def test_w8a8_matmul_compiles_for_v5e(one_chip):
    M, K, N = 768, 1024, 4096
    hlo = _compile(quant_matmul_w8a8, _spec((M, K), jnp.bfloat16, one_chip),
                   _spec((K, N), jnp.int8, one_chip),
                   _spec((N,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo
    # the kernel alone, at a small-batch projection shape
    hlo = _compile(lambda a, b, c, d: w8a8_matmul_pallas(a, b, c, d),
                   _spec((16, 1024), jnp.int8, one_chip),
                   _spec((1024, 1024), jnp.int8, one_chip),
                   _spec((16,), jnp.float32, one_chip),
                   _spec((1024,), jnp.float32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles_for_v5e(one_chip, dtype):
    B, H, S, hd = 8, 16, 96, 64
    qkv = [_spec((B, H, S, hd), dtype, one_chip) for _ in range(3)]
    hlo = _compile(lambda q, k, v, n: flash_attention_pallas(
                       q, k, v, causal=False, interpret=False, kv_len=n),
                   *qkv, _spec((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("window", [0, 64])
def test_token_major_flash_attention_compiles_for_v5e_at_8192(one_chip,
                                                             window, dtype):
    """ModernBERT's attention at its served context: global, and the
    band of |i - j| <= 64 with the tiles its layers use, for bf16
    serving and for the float32 operands of the int8 and fp32 paths
    (whose global tiles must fit VMEM at twice the bytes)."""
    from repro.kernels.flash_attention import flash_attention_tokens_pallas
    from repro.kernels.flash_attention.ops import block_sizes

    B, S, H, hd = 8, 8192, 16, 64
    qkv = [_spec((B, S, H, hd), dtype, one_chip) for _ in range(3)]
    hlo = _compile(lambda q, k, v, n: flash_attention_tokens_pallas(
                       q, k, v, causal=False, window=window, interpret=False,
                       kv_len=n, **block_sizes(window, False,
                                               jnp.dtype(dtype).itemsize)),
                   *qkv, _spec((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


def test_modernbert_embed_step_compiles_for_v5e(one_chip):
    """A whole ModernBERT-large step at its served (8, 8192) batch: the
    local and global attention kernels and pool_norm compile as Mosaic
    kernels, and the step fits the chip."""
    cfg, params, toks, mask = _embed_step_args("bf16", (8, 8192), one_chip,
                                               one_chip, "modernbert-large")
    compiled = jax.jit(lambda p, t, m: embedder.embed(
        p, cfg, t, m, compute_dtype=jnp.bfloat16)).lower(
            params, toks, mask).compile()
    # layer 0, the scanned period's two local layers and one global, pooling
    assert compiled.as_text().count("tpu_custom_call") == 5
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4e9


def _embed_step_args(dtype, bucket, params_sharding, batch_sharding,
                     model="bge-large-zh-v1.5"):
    cfg = get_config(model)
    served = jax.eval_shape(lambda: serve_params(
        embedder.init_embedder(jax.random.PRNGKey(0), cfg), dtype)[0])
    params = jax.tree.map(lambda l: _spec(l.shape, l.dtype, params_sharding),
                          served)
    toks = _spec(bucket, jnp.int32, batch_sharding)
    mask = _spec(bucket, jnp.float32, batch_sharding)
    return cfg, params, toks, mask


@pytest.mark.parametrize("dtype", ["bf16", "int8_w8a8"])
def test_bge_large_embed_step_compiles_on_kernel_route(one_chip, dtype):
    """One whole bge-large embed step at bucket (8, 96), placed on one v5e
    chip with attention on the kernel route: pool_norm, flash attention
    and (int8_w8a8) the quant matmuls all compile as Mosaic kernels."""
    from repro import perf_flags

    cfg, params, toks, mask = _embed_step_args(dtype, (8, 96), one_chip,
                                               one_chip)
    _, cdt = serve_params({}, dtype)
    try:
        perf_flags.set_flags(attn_kernel="auto")
        hlo = _compile(lambda p, t, m: embedder.embed(
            p, cfg, t, m, compute_dtype=cdt,
            act_quant=wants_act_quant(dtype)), params, toks, mask)
    finally:
        perf_flags.reset_flags()
    assert hlo.count("tpu_custom_call") >= (3 if dtype == "int8_w8a8" else 2)


def test_four_chip_data_parallel_embed_step_compiles(topo):
    """The serving backend's step over a 4-chip data-parallel mesh: Mosaic
    kernels cannot be partitioned automatically, so the step runs the
    embedder per shard and the batch stays split over the four chips."""
    from repro.core.sharded_backend import embed_step

    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4, 1),
                             ("data", "model"))
    cfg, params, toks, mask = _embed_step_args(
        "bf16", (16, 96), NamedSharding(mesh, P()),
        NamedSharding(mesh, P("data", None)))
    step = embed_step(cfg, mesh, jnp.bfloat16, act_quant=False)
    compiled = step.lower(params, toks, mask).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-reduce" not in hlo
