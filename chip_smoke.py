#!/usr/bin/env python3
"""Bring-up check of the serving path on a TPU chip.

Serves bge-large-zh-v1.5 at its published width (24 layers, d=1024;
weights drawn from ``--seed``) as the paper's two-tier collaboration, in
this one process: the TPU chip is the accelerator tier and the host CPU
the offload tier, both built by ``repro.launch.serve.build_engine``.

    python chip_smoke.py              # one chip: kernels, engine, burst
    python chip_smoke.py --chips 4    # the 4-chip mesh and replicas only

One chip: every compiled Pallas kernel is checked once against its jnp
reference (run on the host CPU in fp32), the engine is built with
``--prewarm``, a burst of paper-length (75-token) queries larger than the
accelerator depth C_NPU is served, every accepted query must come back as a
finite 1024-d unit vector, both tiers must serve (where C_CPU > 0), the two
tiers must agree on the same queries (cosine >= 0.99997), and no backend
may retrace while serving.

``--chips 4``: the accelerator tier as one 4-chip data-parallel backend,
and as 4 one-chip replicas carved by ``make_replica_meshes``, each checked
against a 1-chip backend on the same queries.

Everything runs in this process, which holds the chip(s).  The script exits
non-zero, printing no result line, when JAX finds no TPU.  The last line of
standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

COSINE_MIN = 0.99
# the TPU tier's fp32 trunk runs its XLA matmuls at JAX's default TPU
# precision (one bf16 pass) against true fp32 on the CPU tier: 0.999987
# measured on a v5e; a bf16-resident tier (0.99994 against fp32, on a
# CPU) fails
TIER_COSINE_MIN = 0.99997
QUERY_LENGTH = 75
SLO_S = 1.0              # the paper's latency SLO
# the burst fills both tiers' depths plus four accelerator batches, so it
# reaches the offload tier under cascade dispatch; C_NPU on a v5e is in
# the low thousands at full width, and this cap bounds the host time spent
# making and submitting the queries
MAX_BURST = 4096


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def cosines(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (a * b).sum(-1) / (np.linalg.norm(a, axis=-1)
                              * np.linalg.norm(b, axis=-1))


def check_unit_vectors(embs, dim: int, what: str) -> None:
    import numpy as np

    arr = np.stack(embs)
    check(arr.shape == (len(embs), dim), f"{what}: shape {arr.shape}")
    check(bool(np.isfinite(arr).all()), f"{what}: non-finite values")
    err = float(np.abs(np.linalg.norm(arr, axis=-1) - 1.0).max())
    check(err < 1e-3, f"{what}: norms off 1 by {err:.2e}")


def check_kernels(tpu, cpu, seed: int) -> None:
    """Each compiled kernel once on the chip against its jnp reference on
    the host CPU, at the serving widths."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import (attention_ref,
                                               flash_attention_pallas)
    from repro.kernels.pool_norm import pool_norm_pallas, pool_norm_ref
    from repro.kernels.quant_matmul import (quant_matmul_pallas,
                                            quant_matmul_ref,
                                            w8a8_matmul_pallas,
                                            w8a8_matmul_ref)

    rng = np.random.default_rng(seed)

    def run(fn, dev, *args):
        return np.asarray(jax.jit(fn)(*[jax.device_put(a, dev)
                                        for a in args]), np.float32)

    def compare(name, kernel, ref, args, rtol):
        got, want = run(kernel, tpu, *args), run(ref, cpu, *args)
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max()) or 1.0
        log(f"kernel {name}: max|err|={err:.3e} max|ref|={scale:.3e} "
            f"(ratio {err / scale:.3e}, bound {rtol:g})")
        check(err <= rtol * scale, f"kernel {name} disagrees with its "
                                   f"reference: {err:.3e}")

    def normal(shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    for (B, S, D), dtype in (((8, 96, 1024), jnp.float32),
                             ((8, 96, 1024), jnp.bfloat16),
                             ((8, 512, 1024), jnp.float32)):
        lens = rng.integers(1, S + 1, B)
        mask = jnp.asarray(np.arange(S)[None] < lens[:, None], jnp.float32)
        h = normal((B, S, D), dtype)
        for pool in ("cls", "mean"):
            compare(f"pool_norm/{pool} {B}x{S}x{D} {jnp.dtype(dtype).name}",
                    functools.partial(pool_norm_pallas, pool=pool,
                                      interpret=False),
                    functools.partial(pool_norm_ref, pool=pool),
                    (h, mask), 1e-4)

    # fp32 operands contract in fp32 inside the kernels; a bf16 pass would
    # miss the fp32 bounds by an order of magnitude
    def bound(dtype, bf16):
        return 1e-4 if dtype == jnp.float32 else bf16

    M, K, N = 768, 1024, 4096
    w8 = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    ws = jnp.asarray(rng.uniform(1e-3, 2e-2, N), jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        compare(f"quant_matmul {M}x{K}x{N} {jnp.dtype(dtype).name}",
                functools.partial(quant_matmul_pallas, interpret=False),
                quant_matmul_ref, (normal((M, K), dtype), w8, ws),
                bound(dtype, 1e-2))
    x8 = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    xs = jnp.asarray(rng.uniform(1e-3, 2e-2, M), jnp.float32)
    compare(f"w8a8_matmul {M}x{K}x{N}",
            functools.partial(w8a8_matmul_pallas, interpret=False),
            w8a8_matmul_ref, (x8, w8, xs, ws), 1e-5)

    B, H, S, hd = 8, 16, 96, 64
    kv_len = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    for dtype in (jnp.float32, jnp.bfloat16):
        q, k, v = (normal((B, H, S, hd), dtype) for _ in range(3))
        compare(f"flash_attention {B}x{H}x{S}x{hd} {jnp.dtype(dtype).name}",
                lambda q, k, v, n: flash_attention_pallas(
                    q, k, v, causal=False, interpret=False, kv_len=n),
                lambda q, k, v, n: attention_ref(
                    q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), causal=False, kv_len=n),
                (q, k, v, kv_len), bound(dtype, 2e-2))


def platforms(backend) -> set:
    return {d.platform for d in backend.mesh.devices.flat}


def one_chip(tpu, cpu, args) -> None:
    import jax
    import numpy as np

    from repro.core.routing import CPU, NPU, Query
    from repro.core.sharded_backend import ShardedEmbedderBackend
    from repro.data.workload import make_queries
    from repro.launch import serve
    from repro.models import embedder

    t0 = time.monotonic()
    check_kernels(tpu, cpu, args.seed)
    log(f"kernel checks passed in {time.monotonic() - t0:.1f}s")

    t0 = time.monotonic()
    engine, cfg = serve.build_engine("bge-large-zh-v1.5", slo=SLO_S,
                                     seed=args.seed, prewarm=True)
    log(f"engine built (prewarm + calibration) in "
        f"{time.monotonic() - t0:.1f}s")
    try:
        tiers = {t.name: t for t in engine.qm.tiers}
        check(NPU in tiers, f"no {NPU} tier: {sorted(tiers)}")
        npu_be = tiers[NPU].backend
        c_npu = tiers[NPU].depth
        c_cpu = tiers[CPU].depth if CPU in tiers else 0
        cpu_be = tiers[CPU].backend if CPU in tiers else None
        log(f"C_NPU={c_npu} backend={npu_be.name}")
        log(f"C_CPU={c_cpu} backend="
            f"{cpu_be.name if cpu_be else 'none (offload depth 0)'}")
        check(platforms(npu_be) == {"tpu"},
              f"accelerator tier on {platforms(npu_be)}")
        if cpu_be is None:
            # the offload tier calibrated to depth 0 and left the topology;
            # build its backend alone for the cross-tier comparison
            params = embedder.init_embedder(jax.random.PRNGKey(args.seed),
                                            cfg)
            cpu_be = ShardedEmbedderBackend(
                cfg, params, max_tokens=cfg.max_tokens, devices=[cpu],
                min_seq_bucket=serve.MIN_SEQ_BUCKET)
        check(platforms(cpu_be) == {"cpu"},
              f"offload tier on {platforms(cpu_be)}")
        traces = {n: be.traces for n, be in engine.backends.items()}
        log(f"traces after prewarm and calibration: {traces}")

        n = min(c_npu + c_cpu + 4 * serve.ACCEL_MAX_BATCH, MAX_BURST)
        check(n > c_npu, f"burst {n} does not exceed C_NPU={c_npu}")
        queries = make_queries(n, cfg.vocab_size, QUERY_LENGTH,
                               seed=args.seed + 1)
        results, failures, rejected, wall = serve.serve_burst(
            engine, queries, QUERY_LENGTH)
        serve.report(engine, n, wall, len(results) - len(failures),
                     len(failures), SLO_S)
        check(not failures, f"{len(failures)} accepted queries failed; "
                            f"first: {failures[:1]!r}")
        check_unit_vectors(results, cfg.d_model, "served embeddings")
        served = engine.stats.per_device
        log(f"burst of {n}: accepted {len(results)}, rejected {rejected}, "
            f"served per tier {served}")
        check(served.get(NPU, 0) > 0, "the accelerator tier served nothing")
        if c_cpu > 0:
            check(served.get(CPU, 0) > 0, "the offload tier served nothing")
        check({n: be.traces for n, be in engine.backends.items()} == traces,
              f"serving retraced: {traces} -> "
              f"{ {n: be.traces for n, be in engine.backends.items()} }")

        sample = [Query(qid=i, payload=p, length=QUERY_LENGTH)
                  for i, p in enumerate(queries[:4])]
        cos = cosines(npu_be.embed_batch(sample), cpu_be.embed_batch(sample))
        log(f"TPU tier vs CPU tier on {len(sample)} queries: cosine min "
            f"{cos.min():.6f}")
        check(bool((cos >= TIER_COSINE_MIN).all()),
              f"tiers disagree: cosines {np.round(cos, 6).tolist()}")
    finally:
        engine.shutdown()


def four_chips(tpus, args) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.routing import Query
    from repro.core.sharded_backend import ShardedEmbedderBackend
    from repro.data.workload import make_queries
    from repro.launch.mesh import make_replica_meshes
    from repro.launch.serve import MIN_SEQ_BUCKET
    from repro.models import embedder

    check(len(tpus) >= 4, f"--chips 4 needs 4 TPU chips, found {len(tpus)}")
    tpus = tpus[:4]
    cfg = get_config("bge-large-zh-v1.5")
    params = embedder.init_embedder(jax.random.PRNGKey(args.seed), cfg)
    kw = dict(max_tokens=cfg.max_tokens, min_seq_bucket=MIN_SEQ_BUCKET)
    qs = [Query(qid=i, payload=p, length=QUERY_LENGTH) for i, p in
          enumerate(make_queries(32, cfg.vocab_size, QUERY_LENGTH,
                                 seed=args.seed + 1))]

    ref = ShardedEmbedderBackend(cfg, params, devices=tpus[:1], **kw)
    want = ref.embed_batch(qs)
    check_unit_vectors(want, cfg.d_model, "1-chip reference")

    # (a) one data-parallel backend over the four chips
    dp = ShardedEmbedderBackend(cfg, params, devices=tpus, **kw)
    check(dp.device_count == 4, f"mesh over {dp.device_count} devices")
    toks, mask, _, _ = dp._stage_chunk(qs, len(qs), cfg.max_tokens)
    out = dp._embed(dp.params, toks, mask)
    out.block_until_ready()
    dp._release_staging([(len(qs), cfg.max_tokens)])
    for arr, what in ((toks, "tokens"), (out, "embeddings")):
        shards = {s.device: s.data.shape for s in arr.addressable_shards}
        log(f"4-chip {what} shards: "
            + ", ".join(f"{d.id}:{shape}" for d, shape in shards.items()))
        check(set(shards) == set(tpus), f"{what} not on all four chips")
        check(all(shape[0] == len(qs) // 4 for shape in shards.values()),
              f"{what} not split evenly: {shards}")
    got = dp.embed_batch(qs)
    check_unit_vectors(got, cfg.d_model, "4-chip embeddings")
    cos = cosines(got, want)
    log(f"4-chip data-parallel vs 1 chip: cosine min {cos.min():.6f} over "
        f"{len(qs)} queries")
    check(bool((cos >= COSINE_MIN).all()), "4-chip backend disagrees")

    # (b) 1 host x 4 replicas, one chip each
    meshes = make_replica_meshes(1, 4, tpus)
    per = len(qs) // len(meshes)
    for r, mesh in enumerate(meshes):
        be = ShardedEmbedderBackend(cfg, params, mesh=mesh, **kw)
        devs = list(mesh.devices.flat)
        check(devs == [tpus[r]], f"replica {r} on {devs}")
        got = be.embed_batch(qs[r * per:(r + 1) * per])
        check_unit_vectors(got, cfg.d_model, f"replica {r} embeddings")
        cos = cosines(got, want[r * per:(r + 1) * per])
        log(f"replica {r} on chip {devs[0].id} vs 1 chip: cosine min "
            f"{cos.min():.6f}")
        check(bool((cos >= COSINE_MIN).all()), f"replica {r} disagrees")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"jax {jax.__version__}, libtpu {libtpu}, compile cache {cache}")
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if not tpus:
        print(f"[smoke] no TPU found: JAX sees "
              f"{sorted({d.platform for d in jax.devices()})}",
              file=sys.stderr)
        return 2
    cpu = jax.devices("cpu")[0]
    log(f"TPU devices: platform={tpus[0].platform} "
        f"kind={tpus[0].device_kind} count={len(tpus)}")
    log(f"offload device: {cpu.platform} {cpu.device_kind} ({os.cpu_count()} "
        f"host cores)")

    t0 = time.monotonic()
    try:
        if args.chips == 4:
            four_chips(tpus, args)
        else:
            one_chip(tpus[0], cpu, args)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    log(f"passed in {time.monotonic() - t0:.1f}s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
