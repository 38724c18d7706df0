"""Int8 weight-only quantized serving path: the load-time transform, the
dense-apply routing, backend/engine composition, per-bucket Eq. 12 fits and
the vectorized tokenizer.

Kernel-level sweeps of ``quant_matmul`` (Pallas interpret vs jnp oracle)
live in ``test_kernels``; this file owns the serving semantics."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import perf_flags
from repro.configs import get_config
from repro.core.bucketing import BucketedEmbedderBackend, length_bucket_fn
from repro.core.estimator import (LatencyFit, estimate_depth,
                                  estimate_depth_per_bucket, quantized_fit)
from repro.core.routing import (CPU, NPU, LengthAwarePolicy, PredictivePolicy,
                                Query, TierSpec)
from repro.core.sharded_backend import ShardedEmbedderBackend
from repro.core.simulator import PAPER_DEVICES, profile_fn_for, quantized_model
from repro.core.windve import JaxEmbedderBackend, WindVE
from repro.models import embedder, layers as L
from repro.models.quantize import (EMBED_DTYPES, is_quantized, quantize_dense,
                                   quantize_params, serve_params,
                                   wants_act_quant)

KEY = jax.random.PRNGKey(0)
MAX_TOKENS = 64


@pytest.fixture(scope="module")
def bge_smoke():
    cfg = get_config("bge-large-zh-v1.5").smoke()
    params = embedder.init_embedder(KEY, cfg)
    return cfg, params


def queries(lengths, payloads=False, vocab=1000, base_qid=0):
    rng = np.random.default_rng(3)
    return [Query(qid=base_qid + i, length=ln,
                  payload=(rng.integers(1, vocab, ln) if payloads else None))
            for i, ln in enumerate(lengths)]


def min_cosine(a, b):
    return float(((a * b).sum(-1) / (np.linalg.norm(a, axis=-1)
                                     * np.linalg.norm(b, axis=-1))).min())


# ------------------------------------------------------ the transform -----
class TestQuantizeParams:
    def test_per_output_channel_scales_and_roundtrip(self):
        w = jax.random.normal(KEY, (96, 130)) * jnp.linspace(0.1, 4.0, 130)
        q, scale = quantize_dense(w)
        assert q.dtype == jnp.int8 and scale.shape == (130,)
        assert int(jnp.abs(q).max()) <= 127
        # per-channel symmetric: every channel uses its own full int8 range
        assert float(jnp.abs(q).max(axis=0).min()) >= 126
        err = jnp.abs(q.astype(jnp.float32) * scale - w)
        # symmetric round-to-nearest: error <= scale/2 per element
        assert bool((err <= scale[None, :] * 0.5 + 1e-7).all())

    def test_zero_channel_gets_unit_scale(self):
        w = jnp.zeros((8, 4)).at[:, 0].set(1.0)
        q, scale = quantize_dense(w)
        assert float(scale[1]) == 1.0 and int(jnp.abs(q[:, 1:]).max()) == 0

    def test_stacked_blocks_quantize_layerwise(self, bge_smoke):
        cfg, params = bge_smoke
        qp = quantize_params(params)
        blk = qp["blocks"]["attn"]
        # stacked (L, K, N) weights -> int8 + per-(layer, channel) scales
        assert blk["wq"].dtype == jnp.int8
        assert blk["wq_scale"].shape == (cfg.num_layers,
                                         blk["wq"].shape[-1])
        # scales are computed per layer, not shared across the stack
        per_layer = [quantize_dense(params["blocks"]["attn"]["wq"][i])[1]
                     for i in range(cfg.num_layers)]
        np.testing.assert_allclose(np.asarray(blk["wq_scale"]),
                                   np.stack(per_layer), rtol=1e-6)

    def test_non_dense_leaves_stay_float(self, bge_smoke):
        cfg, params = bge_smoke
        qp = quantize_params(params)
        assert qp["embed"].dtype == params["embed"].dtype        # gather
        assert qp["final_norm"]["scale"].dtype != jnp.int8
        assert qp["blocks"]["norm1"]["scale"].dtype != jnp.int8
        assert is_quantized(qp) and not is_quantized(params)

    def test_moe_expert_stacks_excluded(self):
        """Expert-stacked weights reuse dense names but bypass dense_apply
        (einsum dispatch) — quantizing them would silently drop the dequant
        scale.  Their extra expert dim is what excludes them, standalone
        (E, D, F) and layer-stacked (L, E, D, F) alike."""
        cfg = get_config("qwen3-moe-30b-a3b").smoke()
        moe = L.init_moe(KEY, cfg, jnp.float32)
        stacked = jax.vmap(lambda _: moe)(jnp.arange(2))   # (L, E, D, F)
        for p in ({"moe": moe}, {"blocks": {"moe": stacked}}):
            qp = quantize_params(p)
            leaf = (qp.get("moe") or qp["blocks"]["moe"])
            assert leaf["w_gate"].dtype != jnp.int8
            assert "w_gate_scale" not in leaf

    def test_serve_params_policies(self, bge_smoke):
        cfg, params = bge_smoke
        t32, c32 = serve_params(params, "fp32")
        assert t32 is params and c32 == jnp.float32
        tb, cb = serve_params(params, "bf16")
        assert tb["embed"].dtype == jnp.bfloat16 and cb == jnp.bfloat16
        t8, c8 = serve_params(params, "int8")
        assert is_quantized(t8) and c8 == jnp.float32
        ta, ca = serve_params(params, "int8_w8a8")
        assert is_quantized(ta) and ca == jnp.float32
        with pytest.raises(ValueError, match="fp32|bf16|int8"):
            serve_params(params, "fp16")
        assert set(EMBED_DTYPES) == {"fp32", "bf16", "int8", "int8_w8a8"}
        assert wants_act_quant("int8_w8a8")
        assert not any(wants_act_quant(d) for d in ("fp32", "bf16", "int8",
                                                    None))

    def test_unknown_embed_dtype_rejected_both_spellings(self, bge_smoke):
        """Both entry points name the FULL valid set (incl. int8_w8a8) when
        rejecting a policy: serve_params at backend construction and
        parse_opt at the CLI."""
        cfg, params = bge_smoke
        with pytest.raises(ValueError) as e1:
            serve_params(params, "w8a8")
        with pytest.raises(ValueError) as e2:
            perf_flags.parse_opt("embed_dtype=w8a8")
        for err in (str(e1.value), str(e2.value)):
            for valid in EMBED_DTYPES:
                assert valid in err
            assert "w8a8'" in err or "'w8a8'" in err
        # the value check guards parse time, not just first backend build
        with pytest.raises(ValueError, match="int8_w8a8"):
            perf_flags.parse_opt("embed_donate=1,embed_dtype=int9")


# ------------------------------------------------- dense-apply routing ----
class TestDenseApplyRouting:
    def test_float_path_unchanged(self):
        p = {"wq": jax.random.normal(KEY, (32, 48))}
        x = jax.random.normal(KEY, (4, 32))
        np.testing.assert_array_equal(
            np.asarray(L.dense_apply(p, "wq", x)),
            np.asarray(x @ p["wq"]))

    def test_quantized_path_close_to_float(self):
        w = jax.random.normal(KEY, (64, 96))
        q, s = quantize_dense(w)
        p = {"wo": q, "wo_scale": s}
        x = jax.random.normal(KEY, (8, 64))
        got = np.asarray(L.dense_apply(p, "wo", x))
        want = np.asarray(x @ w)
        assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()

    def test_act_quant_routes_w8a8(self, monkeypatch):
        """With a scale sibling AND act_quant on, dense_apply must take the
        W8A8 kernel (and stay on weight-only / plain matmul otherwise)."""
        from repro.kernels.quant_matmul import ops as qm_ops

        calls = []
        monkeypatch.setattr(
            qm_ops, "quant_matmul_w8a8",
            lambda x, w8, s, **kw: calls.append("w8a8") or x @ w8.astype(
                x.dtype))
        monkeypatch.setattr(
            qm_ops, "quant_matmul",
            lambda x, w8, s, **kw: calls.append("w8") or x @ w8.astype(
                x.dtype))
        w = jax.random.normal(KEY, (16, 24))
        q, s = quantize_dense(w)
        x = jax.random.normal(KEY, (4, 16))
        L.dense_apply({"wq": q, "wq_scale": s}, "wq", x, act_quant=True)
        L.dense_apply({"wq": q, "wq_scale": s}, "wq", x, act_quant=False)
        L.dense_apply({"wq": w}, "wq", x, act_quant=True)   # float: no-op
        assert calls == ["w8a8", "w8"]

    def test_w8a8_path_close_to_float(self):
        w = jax.random.normal(KEY, (64, 96))
        q, s = quantize_dense(w)
        p = {"wo": q, "wo_scale": s}
        x = jax.random.normal(KEY, (8, 64))
        got = np.asarray(L.dense_apply(p, "wo", x, act_quant=True))
        want = np.asarray(x @ w)
        assert np.abs(got - want).max() <= 0.05 * np.abs(want).max()
        assert got.dtype == want.dtype

    @pytest.mark.parametrize("model,pool", [("bge-large-zh-v1.5", "cls"),
                                            ("jina-v2", "mean")])
    @pytest.mark.parametrize("dtype,bar", [("int8", 0.99),
                                           ("int8_w8a8", 0.98)])
    def test_embedder_quantized_cosine_parity(self, model, pool, dtype, bar):
        """Acceptance guard: int8 trunk >= 0.99 and W8A8 trunk >= 0.98
        cosine vs the fp32 oracle for BOTH paper model families (cls and
        mean pooling)."""
        cfg = get_config(model).smoke()
        assert cfg.pool == pool
        params = embedder.init_embedder(KEY, cfg)
        qp, cdt = serve_params(params, dtype)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 40), 1,
                                  cfg.vocab_size)
        mask = (jnp.arange(40)[None, :] <
                jnp.asarray([[40], [22], [9], [33]])).astype(jnp.float32)
        a = np.asarray(embedder.embed(params, cfg, toks, mask,
                                      compute_dtype=jnp.float32))
        b = np.asarray(embedder.embed(qp, cfg, toks, mask,
                                      compute_dtype=cdt,
                                      act_quant=wants_act_quant(dtype)))
        assert b.dtype == np.float32
        np.testing.assert_allclose(np.linalg.norm(b, axis=-1), 1.0,
                                   atol=1e-3)
        assert min_cosine(a, b) >= bar


# ------------------------------------------------- serving backends -------
class TestInt8Backends:
    def test_all_three_backends_agree(self, bge_smoke):
        """Fixed, bucketed and 1-device sharded int8 paths serve the same
        vectors (the bucketed/sharded degrade contract, quantized)."""
        cfg, params = bge_smoke
        qs = queries([12, 30, 55, 20, 44, 9], payloads=True,
                     vocab=cfg.vocab_size)
        fix = JaxEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                 dtype="int8")
        buck = BucketedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                       min_seq_bucket=8, dtype="int8")
        shard = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                       min_seq_bucket=8, dtype="int8")
        a = np.stack(fix.embed_batch(qs))
        b = np.stack(buck.embed_batch(qs))
        c = np.stack(shard.embed_batch(qs))
        np.testing.assert_allclose(a, b, atol=1e-5)
        np.testing.assert_allclose(b, c, atol=1e-5)
        assert "int8" in fix.name and "int8" in buck.name \
            and "int8" in shard.name

    def test_sharded_int8_parity_and_footprint(self, bge_smoke):
        cfg, params = bge_smoke
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                        dtype="fp32")
        i8 = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    dtype="int8")
        qs = queries([12, 30, 55, 20, 44, 9], payloads=True,
                     vocab=cfg.vocab_size)
        a = np.stack(oracle.embed_batch(qs))
        b = np.stack(i8.embed_batch(qs))
        assert a.dtype == b.dtype == np.float32
        assert min_cosine(a, b) >= 0.99
        # weight-only: projections are 1 byte/element, so the resident tree
        # shrinks (the smoke embed table is fp32 and relatively large)
        assert i8.params_nbytes < 0.5 * oracle.params_nbytes
        assert i8.serve_dtype == jnp.float32          # fp32 activations

    def test_prewarm_then_zero_serving_retraces(self, bge_smoke):
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    min_seq_bucket=8, dtype="int8",
                                    donate=True, async_dispatch=True)
        grid = be.warm_grid(max_batch=4)
        n = be.prewarm(grid)
        assert n == len(grid) == be.traces
        for lens in ([5], [9, 9], [40, 33, 20], [7, 7, 7, 60]):
            be.embed_batch(queries(lens))
        assert be.traces == n, "int8 serving retraced despite prewarm"
        assert be.bucket_hits > 0

    def test_flag_selects_int8_default(self, bge_smoke):
        cfg, params = bge_smoke
        try:
            perf_flags.set_flags(embed_dtype="int8")
            be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
            assert be.dtype == "int8"
            assert is_quantized(be.params)
        finally:
            perf_flags.reset_flags()

    def test_parse_opt_int8_roundtrip(self):
        kw = perf_flags.parse_opt("embed_dtype=int8,embed_donate=1")
        assert kw == {"embed_dtype": "int8", "embed_donate": True}
        flags = perf_flags.set_flags(**kw)
        assert flags.embed_dtype == "int8"
        perf_flags.reset_flags()

    def test_engine_serves_int8_with_bucketing_async_donate(self, bge_smoke):
        """embed_dtype=int8 composes with donation, async dispatch and
        length-aware bucketed batch formation under the real engine; every
        future receives ITS query's embedding (>= 0.99 cosine vs the fp32
        oracle serving the same payload)."""
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                                    min_seq_bucket=8, dtype="int8",
                                    donate=True, async_dispatch=True)
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                                        min_seq_bucket=8, dtype="fp32")
        rng = np.random.default_rng(11)
        payloads = [rng.integers(1, cfg.vocab_size, 20) for _ in range(12)]
        ve = WindVE(tiers=[TierSpec(NPU, 64, backend=be, max_batch=3,
                                    bucket_fn=length_bucket_fn(8, 32))])
        try:
            futs = [ve.submit(payload=p, length=len(p)) for p in payloads]
            got = [f.result(timeout=60) for f in futs]
        finally:
            ve.shutdown()
        want = oracle.embed_batch(
            [Query(qid=100 + i, payload=p, length=len(p))
             for i, p in enumerate(payloads)])
        for g, w in zip(got, want):
            assert min_cosine(np.asarray(g)[None], np.asarray(w)[None]) \
                >= 0.99


# ------------------------------------------------- W8A8 serving ----------
class TestW8A8Backends:
    def test_all_three_backends_agree(self, bge_smoke):
        """Fixed, bucketed and 1-device sharded W8A8 paths serve the same
        vectors (the bucketed/sharded degrade contract, fully quantized)."""
        cfg, params = bge_smoke
        qs = queries([12, 30, 55, 20, 44, 9], payloads=True,
                     vocab=cfg.vocab_size)
        fix = JaxEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                 dtype="int8_w8a8")
        buck = BucketedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                       min_seq_bucket=8, dtype="int8_w8a8")
        shard = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                       min_seq_bucket=8, dtype="int8_w8a8")
        assert fix.act_quant and buck.act_quant and shard.act_quant
        a = np.stack(fix.embed_batch(qs))
        b = np.stack(buck.embed_batch(qs))
        c = np.stack(shard.embed_batch(qs))
        np.testing.assert_allclose(a, b, atol=1e-5)
        np.testing.assert_allclose(b, c, atol=1e-5)
        assert "int8_w8a8" in fix.name and "int8_w8a8" in buck.name \
            and "int8_w8a8" in shard.name

    def test_sharded_w8a8_parity_and_footprint(self, bge_smoke):
        cfg, params = bge_smoke
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                        dtype="fp32")
        i8 = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    dtype="int8")
        w8a8 = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                      dtype="int8_w8a8")
        qs = queries([12, 30, 55, 20, 44, 9], payloads=True,
                     vocab=cfg.vocab_size)
        a = np.stack(oracle.embed_batch(qs))
        b = np.stack(w8a8.embed_batch(qs))
        assert a.dtype == b.dtype == np.float32
        np.testing.assert_allclose(np.linalg.norm(b, axis=-1), 1.0,
                                   atol=1e-3)
        assert min_cosine(a, b) >= 0.98
        # same resident tree as weight-only int8 — activation quantization
        # is a trace-time choice, not a second copy of the weights
        assert w8a8.params_nbytes == i8.params_nbytes
        assert w8a8.serve_dtype == jnp.float32   # trunk dequantizes to fp32
        assert not oracle.act_quant and not i8.act_quant and w8a8.act_quant

    def test_prewarm_then_zero_serving_retraces(self, bge_smoke):
        """W8A8 composes with donation + async dispatch + bucketing and the
        dynamic activation quantization does NOT add steady-state retraces
        (the per-batch scales are traced values, not cache keys)."""
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    min_seq_bucket=8, dtype="int8_w8a8",
                                    donate=True, async_dispatch=True)
        grid = be.warm_grid(max_batch=4)
        n = be.prewarm(grid)
        assert n == len(grid) == be.traces
        for lens in ([5], [9, 9], [40, 33, 20], [7, 7, 7, 60]):
            be.embed_batch(queries(lens))
        assert be.traces == n, "w8a8 serving retraced despite prewarm"
        assert be.bucket_hits > 0

    def test_flag_selects_w8a8_default(self, bge_smoke):
        cfg, params = bge_smoke
        try:
            perf_flags.set_flags(embed_dtype="int8_w8a8")
            be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
            assert be.dtype == "int8_w8a8"
            assert is_quantized(be.params) and be.act_quant
        finally:
            perf_flags.reset_flags()

    def test_parse_opt_w8a8_roundtrip(self):
        kw = perf_flags.parse_opt("embed_dtype=int8_w8a8,embed_donate=1")
        assert kw == {"embed_dtype": "int8_w8a8", "embed_donate": True}
        flags = perf_flags.set_flags(**kw)
        assert flags.embed_dtype == "int8_w8a8"
        perf_flags.reset_flags()

    def test_engine_serves_w8a8_with_bucketing_async_donate(self, bge_smoke):
        """embed_dtype=int8_w8a8 composes with donation, async dispatch and
        length-aware bucketed batch formation under the real engine; every
        future receives ITS query's embedding (>= 0.98 cosine vs the fp32
        oracle serving the same payload)."""
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                                    min_seq_bucket=8, dtype="int8_w8a8",
                                    donate=True, async_dispatch=True)
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                                        min_seq_bucket=8, dtype="fp32")
        rng = np.random.default_rng(11)
        payloads = [rng.integers(1, cfg.vocab_size, 20) for _ in range(12)]
        ve = WindVE(tiers=[TierSpec(NPU, 64, backend=be, max_batch=3,
                                    bucket_fn=length_bucket_fn(8, 32))])
        try:
            futs = [ve.submit(payload=p, length=len(p)) for p in payloads]
            got = [f.result(timeout=60) for f in futs]
        finally:
            ve.shutdown()
        want = oracle.embed_batch(
            [Query(qid=100 + i, payload=p, length=len(p))
             for i, p in enumerate(payloads)])
        for g, w in zip(got, want):
            assert min_cosine(np.asarray(g)[None], np.asarray(w)[None]) \
                >= 0.98


# ------------------------------------- quantized-tier calibration ---------
class TestQuantizedCalibration:
    """Satellite: the measured W8A8 ``beta_s`` feeds back into the Eq. 12
    machinery, so depth estimation and predictive dispatch price the
    quantized tier correctly."""

    def test_quantized_fit_scales_slope_only(self):
        fit = LatencyFit(alpha=0.1, beta=0.3, r2=0.99)
        qf = quantized_fit(fit, 0.6)
        assert qf.alpha == pytest.approx(0.06)
        assert qf.beta == fit.beta and qf.r2 == fit.r2
        with pytest.raises(ValueError):
            quantized_fit(fit, 0.0)

    def test_w8a8_modeled_depth_at_least_fp32(self):
        """Fitted depth for the W8A8-modeled backend >= fp32 depth on the
        same device (and strictly greater when the slope actually binds)."""
        dev = PAPER_DEVICES["xeon-e5-2690/bge"]
        d_f32, fit_f32 = estimate_depth(profile_fn_for(dev), 2.0)
        q = quantized_model(dev, 0.6)
        d_q, fit_q = estimate_depth(profile_fn_for(q), 2.0)
        assert d_q >= d_f32 > 0
        assert d_q > d_f32          # 0.6x slope must buy real depth at 2s
        assert fit_q.alpha < fit_f32.alpha
        # the offline shortcut (scale the fp32 fit) prices the quantized
        # tier like re-profiling the scaled device does
        short = quantized_fit(fit_f32, 0.6)
        assert short.max_concurrency(2.0) > d_f32
        assert short.max_concurrency(2.0) == pytest.approx(d_q, rel=0.15)
        with pytest.raises(ValueError):
            quantized_model(dev, -1.0)

    def test_per_bucket_w8a8_depths_dominate_fp32(self):
        dev = PAPER_DEVICES["xeon-e5-2690/bge"]
        q = quantized_model(dev, 0.5)

        def profile(d):
            return lambda c, length: d.latency(c, length)

        f32 = estimate_depth_per_bucket(profile(dev), 2.0, [16, 64, 128])
        w8 = estimate_depth_per_bucket(profile(q), 2.0, [16, 64, 128])
        assert all(w8[b][0] >= f32[b][0] for b in (16, 64, 128))
        assert any(w8[b][0] > f32[b][0] for b in (16, 64, 128))

    def test_predictive_policy_prefers_w8a8_tier_at_equal_backlog(self):
        """Two CPU tiers, same device, one serving W8A8: at equal backlog
        the predictive policy must order the quantized tier first."""
        from repro.core.queue_manager import QueueManager

        base = LatencyFit(alpha=0.2, beta=0.3, r2=1.0)
        pol = PredictivePolicy(fits={CPU: base,
                                     "CPU-w8a8": quantized_fit(base, 0.5)})
        tiers = [TierSpec(CPU, 8), TierSpec("CPU-w8a8", 8)]
        qm = QueueManager(tiers)
        for i in range(3):      # equal backlog on both tiers
            assert qm.queues[CPU].push(Query(qid=i, length=20))
            assert qm.queues["CPU-w8a8"].push(Query(qid=10 + i, length=20))
        order = pol.candidates(Query(qid=99, length=20), tiers, qm)
        assert order[0] == "CPU-w8a8"


# ---------------------------------------------- per-bucket Eq. 12 fits ----
class TestPerBucketDepths:
    def test_per_bucket_fits_recover_linear_curves(self):
        # alpha grows with bucket length (Fig. 5's collapse), beta fixed
        def profile(c, length):
            return 0.001 * length * c + 0.05

        fits = estimate_depth_per_bucket(profile, 1.0, [16, 64, 128],
                                         probe_points=(1, 2, 4, 8))
        assert set(fits) == {16, 64, 128}
        d16, f16 = fits[16]
        d128, f128 = fits[128]
        assert f16.alpha == pytest.approx(0.016, rel=1e-6)
        assert f128.alpha == pytest.approx(0.128, rel=1e-6)
        assert d16 > d128          # short buckets sustain deeper queues
        assert d16 == int((1.0 - 0.05) / 0.016)

    def test_threshold_from_first_collapsed_bucket(self):
        pol = LengthAwarePolicy.from_bucket_depths({16: 40, 32: 9, 64: 0,
                                                    128: 0})
        # queries round UP into their bucket, so anything ABOVE the last
        # live bucket (32) pads into the dead 64-bucket and must be long
        assert pol.long_threshold == 33
        tiers = [TierSpec(NPU, 4), TierSpec("CPU", 4)]
        assert pol.candidates(Query(qid=1, length=40), tiers, None) == [NPU]
        assert pol.candidates(Query(qid=2, length=32), tiers, None) \
            == [NPU, "CPU"]

    def test_threshold_when_smallest_bucket_collapses(self):
        # every length pads into a dead bucket -> every query is long
        pol = LengthAwarePolicy.from_bucket_depths({16: 0, 32: 0})
        assert pol.long_threshold == 1
        tiers = [TierSpec(NPU, 4), TierSpec("CPU", 4)]
        assert pol.candidates(Query(qid=1, length=2), tiers, None) == [NPU]

    def test_threshold_when_no_bucket_collapses(self):
        # unprofiled lengths must not ride the slow tier on faith
        pol = LengthAwarePolicy.from_bucket_depths({16: 40, 96: 5})
        assert pol.long_threshold == 97

    def test_empty_depths_rejected(self):
        with pytest.raises(ValueError):
            LengthAwarePolicy.from_bucket_depths({})

    def test_real_backend_bucket_curves_are_monotone_in_length(self,
                                                               bge_smoke):
        """On the real int8 backend a longer bucket costs at least as much
        per batch (warm, best-of-3) — the structure the per-bucket fits
        feed into the policy."""
        import time as _t

        cfg, params = bge_smoke
        be = BucketedEmbedderBackend(cfg, params, max_tokens=128,
                                     min_seq_bucket=16, dtype="int8")

        def profile(c, length):
            batch = queries([length] * c, base_qid=length * 100)
            be.embed_batch(batch)          # warm this (c, length) bucket
            best = float("inf")
            for _ in range(3):
                t0 = _t.monotonic()
                be.embed_batch(batch)
                best = min(best, _t.monotonic() - t0)
            return best

        t16 = profile(4, 16)
        t128 = profile(4, 128)
        assert t128 > t16 * 1.5


# ------------------------------------------------ vectorized tokenizer ----
class TestVectorizedTokenize:
    @staticmethod
    def _reference(cfg, qs, seq_len):
        toks = np.zeros((len(qs), seq_len), np.int32)
        mask = np.zeros((len(qs), seq_len), np.float32)
        real = truncated = 0
        for i, q in enumerate(qs):
            ids = q.payload
            if ids is None:
                ids = (np.arange(q.length) % (cfg.vocab_size - 1)) + 1
            if len(ids) > seq_len:
                truncated += 1
            n = min(len(ids), seq_len)
            toks[i, :n] = np.asarray(ids[:n], np.int32)
            mask[i, :n] = 1.0
            real += n
        return toks, mask, real, truncated

    def test_matches_loop_reference_mixed_batch(self, bge_smoke):
        cfg, params = bge_smoke
        be = JaxEmbedderBackend(cfg, params, max_tokens=32)
        rng = np.random.default_rng(5)
        qs = [Query(qid=1, length=10),                       # synthetic
              Query(qid=2, length=40,
                    payload=rng.integers(1, 500, 40)),       # truncated
              Query(qid=3, length=50),                       # synth trunc
              Query(qid=4, length=3, payload=[7, 8, 9]),     # list payload
              Query(qid=5, length=32,
                    payload=rng.integers(1, 500, 32))]       # exact fit
        got = be._tokenize(qs, 32)
        want = self._reference(cfg, qs, 32)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        assert got[2:] == want[2:]

    def test_out_buffer_rows_beyond_batch_zeroed(self, bge_smoke):
        cfg, params = bge_smoke
        be = JaxEmbedderBackend(cfg, params, max_tokens=32)
        out = (np.full((6, 16), 9, np.int32), np.full((6, 16), 9.0,
                                                      np.float32))
        qs = queries([10, 12], payloads=True, vocab=400)
        toks, mask, real, trunc = be._tokenize(qs, 16, out=out)
        assert toks is out[0] and mask is out[1]
        assert (toks[2:] == 0).all() and (mask[2:] == 0.0).all()
        want = self._reference(cfg, qs, 16)
        np.testing.assert_array_equal(toks[:2], want[0])
        assert (real, trunc) == want[2:]

    def test_empty_batch(self, bge_smoke):
        cfg, params = bge_smoke
        be = JaxEmbedderBackend(cfg, params, max_tokens=32)
        toks, mask, real, trunc = be._tokenize([], 16)
        assert toks.shape == (0, 16) and real == 0 and trunc == 0
