"""Device-sharded serving path: serve-mode spec rules, mesh degrade
behaviour, bf16-vs-fp32 parity, donation/async correctness, staging reuse
and the engine's pipelined worker.

The spec-rule tests use the FakeMesh idiom from ``test_sharding`` (axis
names/sizes only, no real devices); the real multi-device mesh runs in a
subprocess with a forced 8-device host platform, because the device count is
fixed at jax backend init and the suite must keep seeing one device (see
``conftest``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.bucketing import BucketedEmbedderBackend, length_bucket_fn
from repro.core.routing import NPU, Query, TierSpec
from repro.core.sharded_backend import ShardedEmbedderBackend, _serve_devices
from repro.core.telemetry import Telemetry
from repro.core.windve import WindVE
from repro.models import embedder
from repro.parallel import sharding
from tests.test_sharding import FakeMesh

MAX_TOKENS = 64


@pytest.fixture(scope="module")
def bge_smoke():
    cfg = get_config("bge-large-zh-v1.5").smoke()
    params = embedder.init_embedder(jax.random.PRNGKey(0), cfg)
    return cfg, params


def queries(lengths, base_qid=0, payloads=False, vocab=1000):
    rng = np.random.default_rng(3)
    return [Query(qid=base_qid + i, length=ln,
                  payload=(rng.integers(1, vocab, ln) if payloads else None))
            for i, ln in enumerate(lengths)]


def cosine_distance(a, b):
    return float((1.0 - (a * b).sum(-1) /
                  (np.linalg.norm(a, axis=-1) *
                   np.linalg.norm(b, axis=-1))).max())


# ------------------------------------------------- serve-mode spec rules --
class TestServeModeSpecs:
    """Satellite: serve-mode sharding rules for the embedder param tree over
    a multi-device data-parallel host mesh (8 x 1)."""

    MESH = FakeMesh({"data": 8, "model": 1})

    def _specs(self, bge_smoke):
        cfg, params = bge_smoke
        shape = jax.eval_shape(lambda: params)
        return sharding.param_pspecs(self.MESH, shape, mode="serve")

    def test_weights_resident_no_data_axis_specs(self, bge_smoke):
        specs = self._specs(bge_smoke)
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert flat, "no specs produced for the embedder tree"
        for spec in flat:
            for entry in tuple(spec):
                axes = entry if isinstance(entry, tuple) else (entry,)
                assert "data" not in axes, \
                    f"serve-mode spec {spec} FSDP-shards a weight over data"

    def test_train_mode_does_shard_weights_over_data(self, bge_smoke):
        cfg, params = bge_smoke
        shape = jax.eval_shape(lambda: params)
        train = sharding.param_pspecs(self.MESH, shape, mode="train")
        flat = jax.tree.leaves(train, is_leaf=lambda x: isinstance(x, P))
        assert any("data" in (e if isinstance(e, tuple) else (e,))
                   for s in flat for e in tuple(s)), \
            "train mode lost its FSDP specs — serve test would be vacuous"

    def test_batch_shards_over_data(self):
        assert sharding.dp_axes(self.MESH) == ("data",)
        # the (B, S) token/mask batch (and the (B, D) output) shard over the
        # mesh's data axes and replicate the trailing dim
        dp = sharding.dp_axes(self.MESH)
        b = dp if len(dp) > 1 else dp[0]
        assert P(b, None) == P("data", None)


# ---------------------------------------------- single-device mesh (real) --
class TestShardedBackendSingleDevice:
    def test_degrades_to_bucketed_backend(self, bge_smoke):
        """bf16-resident weights == the bucketed path's cast-at-use weights
        (fp32->bf16 rounding commutes with the gather), so a single-device
        mesh serves bitwise-identical vectors to PR 2's backend."""
        cfg, params = bge_smoke
        buck = BucketedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                       min_seq_bucket=8)
        shard = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                       min_seq_bucket=8, dtype="bf16")
        assert shard.device_count == 1
        for lens in ([10, 40, 25], [5], [33, 7, 60, 12, 50]):
            a = np.stack(buck.embed_batch(queries(lens)))
            b = np.stack(shard.embed_batch(queries(lens)))
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_bf16_parity_with_fp32_oracle(self, bge_smoke):
        """Acceptance guard: bf16 serving stays within 1e-2 cosine of the
        fp32 oracle (fp32-resident weights + fp32 trunk); both emit fp32
        unit vectors because the pool_norm epilogue accumulates fp32."""
        cfg, params = bge_smoke
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                        dtype="fp32")
        bf16 = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                      dtype="bf16")
        qs = queries([12, 30, 55, 20, 44, 9], payloads=True,
                     vocab=cfg.vocab_size)
        a = np.stack(oracle.embed_batch(qs))
        b = np.stack(bf16.embed_batch(qs))
        assert a.dtype == b.dtype == np.float32
        np.testing.assert_allclose(np.linalg.norm(b, axis=-1), 1.0,
                                   atol=1e-3)
        assert cosine_distance(a, b) <= 1e-2

    def test_donate_and_async_serve_identical_vectors(self, bge_smoke):
        cfg, params = bge_smoke
        base = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
        opt = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                     donate=True, async_dispatch=True)
        assert opt.async_dispatch and opt.donate
        qs = queries([18, 33, 7, 61])
        a = np.stack(base.embed_batch(qs))
        fetch = opt.embed_batch_async(qs)
        b = np.stack(fetch())
        np.testing.assert_allclose(a, b, atol=1e-5)

    def test_flags_pick_backend_defaults(self, bge_smoke):
        from repro import perf_flags

        cfg, params = bge_smoke
        try:
            perf_flags.set_flags(embed_dtype="bf16", embed_donate=True)
            be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
            assert be.serve_dtype == jnp.bfloat16
            assert be.donate
            # the drain follows the mesh's platform: a CPU mesh drains
            # synchronously whatever the flags say
            assert be.platform == "cpu" and not be.async_dispatch
        finally:
            perf_flags.reset_flags()
        base = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
        assert base.serve_dtype == jnp.float32
        assert not base.donate and not base.async_dispatch
        forced = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                        async_dispatch=True)
        assert forced.async_dispatch

    def test_staging_ring_bounded_and_reused_per_bucket(self, bge_smoke):
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    min_seq_bucket=8)
        for _ in range(be._staging_slots + 2):         # bucket (4, 16)
            be.embed_batch(queries([10, 12, 9, 15]))
        assert set(be._staging) == {(4, 16)}
        ring = be._staging[(4, 16)]
        assert len(ring) == be._staging_slots          # bounded...
        ids = [(id(t), id(m)) for t, m in ring]
        be.embed_batch(queries([16, 11, 13, 14]))      # same bucket
        assert [(id(t), id(m))
                for t, m in be._staging[(4, 16)]] == ids   # ...then reused
        be.embed_batch(queries([40, 50]))              # new bucket (2, 64)
        assert set(be._staging) == {(4, 16), (2, 64)}

    def test_prewarm_then_zero_serving_retraces(self, bge_smoke):
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    min_seq_bucket=8,
                                    dtype="bf16", donate=True,
                                    async_dispatch=True)
        grid = be.warm_grid(max_batch=4)
        n = be.prewarm(grid)
        assert n == len(grid) == be.traces
        for lens in ([5], [9, 9], [40, 33, 20], [7, 7, 7, 60]):
            be.embed_batch(queries(lens))
        assert be.traces == n, "sharded serving retraced despite prewarm"

    def test_truncation_counts_into_telemetry(self, bge_smoke):
        cfg, params = bge_smoke
        tel = Telemetry()
        be = ShardedEmbedderBackend(cfg, params, max_tokens=16,
                                    telemetry=tel)
        be.embed_batch([Query(qid=1, payload=np.arange(1, 40), length=39)])
        assert be.truncated == 1 and tel.truncated == 1

    def test_rejects_unknown_dtype(self, bge_smoke):
        cfg, params = bge_smoke
        with pytest.raises(ValueError, match="fp32|bf16"):
            ShardedEmbedderBackend(cfg, params, dtype="fp16")


# ----------------------------------------------- staging overrun guard --
class TestStagingOverrun:
    """The ROADMAP's 'fetch at most 2 batches late' discipline, enforced:
    more concurrent staged-but-unfetched batches than the ring has slots
    must raise a clear error (never serve rotated embeddings)."""

    def _batches(self, cfg, n, base=0):
        rng = np.random.default_rng(100 + base)
        return [[Query(qid=base * 100 + i * 10 + j,
                       payload=rng.integers(1, cfg.vocab_size, 10),
                       length=10) for j in range(4)] for i in range(n)]

    def test_three_workers_default_slots_raise_clearly_or_serve_correct(
            self, bge_smoke):
        import threading

        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
        barrier = threading.Barrier(3, timeout=30)
        errors, served = [], []
        lock = threading.Lock()

        def worker(tid):
            b0, b1 = self._batches(cfg, 2, base=tid)
            f0 = be.embed_batch_async(b0)       # 3 staged, none fetched
            barrier.wait()
            err = f1 = None
            try:
                f1 = be.embed_batch_async(b1)   # 4th-6th staging: overrun
            except RuntimeError as e:
                err = e
            barrier.wait()  # every thread attempts round 2 BEFORE any fetch
            if err is not None:
                with lock:
                    errors.append(err)
                f0()                            # release what we hold
                return
            with lock:
                served.append((b0, f0()))
                served.append((b1, f1()))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        # default staging_slots (4) covers 2 pipelined workers; three
        # must either trip the guard loudly or still serve correct vectors
        assert errors, "3 workers on default staging_slots went unguarded"
        for e in errors:
            assert "staging_slots" in str(e) and "overrun" in str(e)
        for batch, embs in served:              # survivors stay correct
            want = oracle.embed_batch(batch)
            np.testing.assert_allclose(np.stack(embs), np.stack(want),
                                       atol=1e-5)

    def _drive(self, be, cfg, n_workers, n_batches):
        import threading

        errors, served = [], []
        lock = threading.Lock()

        def worker(tid):
            pending = None
            try:
                for batch in self._batches(cfg, n_batches, base=tid):
                    fetch = be.embed_batch_async(batch)
                    if pending is not None:
                        pb, pf = pending
                        with lock:
                            served.append((pb, pf()))
                    pending = (batch, fetch)
                if pending is not None:
                    pb, pf = pending
                    with lock:
                        served.append((pb, pf()))
            except Exception as e:              # pragma: no cover - fail path
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        return errors, served

    def test_two_workers_double_buffering_never_trips_the_guard(
            self, bge_smoke):
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
        errors, served = self._drive(be, cfg, n_workers=2, n_batches=6)
        assert not errors, errors
        assert len(served) == 12
        for batch, embs in served:
            np.testing.assert_allclose(
                np.stack(embs), np.stack(oracle.embed_batch(batch)),
                atol=1e-5)
        assert not be._staging_pending          # accounting drained

    def test_raised_staging_slots_covers_three_workers(self, bge_smoke):
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    staging_slots=6)     # 2 x 3 workers
        errors, served = self._drive(be, cfg, n_workers=3, n_batches=5)
        assert not errors, errors
        assert len(served) == 15
        assert not be._staging_pending


# ---------------------------------------------- engine pipelined drain --
class TestEngineAsyncWorker:
    def test_async_backend_serves_correct_futures(self, bge_smoke):
        """The pipelined worker must hand every future ITS OWN batch's
        embedding (a lag bug would rotate results between batches)."""
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                                    dtype="bf16", async_dispatch=True)
        oracle = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                                        dtype="bf16")
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
            np.testing.assert_allclose(g, w, atol=1e-5)
        assert ve.stats.batch_latencies, "worker did not record batch tails"

    def test_sync_backend_records_batch_latency(self, bge_smoke):
        cfg, params = bge_smoke
        be = ShardedEmbedderBackend(cfg, params, max_tokens=32)
        ve = WindVE(tiers=[TierSpec(NPU, 16, backend=be)])
        try:
            fut = ve.submit(length=12)
            fut.result(timeout=60)
        finally:
            ve.shutdown()
        s = ve.stats.summary()
        assert len(ve.stats.batch_latencies) >= 1
        assert s["batch_p95_s"] >= s["batch_p50_s"] >= 0.0


# -------------------------------------------------- telemetry percentiles --
class TestBatchTailTelemetry:
    def test_summary_surfaces_batch_percentiles(self):
        t = Telemetry()
        for ms in (1, 2, 3, 4, 100):
            t.record_batch(NPU, ms / 1e3)
        s = t.summary()
        assert s["batch_p50_s"] == pytest.approx(3e-3)
        assert s["batch_p99_s"] > s["batch_p95_s"] > s["batch_p50_s"]
        assert t.batch_p(50) == s["batch_p50_s"]

    def test_empty_batch_percentiles_are_zero(self):
        s = Telemetry().summary()
        assert s["batch_p50_s"] == s["batch_p95_s"] == s["batch_p99_s"] == 0.0

    def test_des_records_batch_latencies(self):
        from repro.core.simulator import PAPER_DEVICES, ServingSimulator

        npu = PAPER_DEVICES["tesla-v100/bge"]
        res = ServingSimulator(npu, None, 16, 0, slo_s=2.0).run_burst(32)
        assert res.batch_latencies
        assert res.batch_p(95) >= res.batch_p(50) > 0.0


# ----------------------------------------------- real 8-device host mesh --
_SUBPROCESS_PROBE = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import numpy as np
import jax
from repro.configs import get_config
from repro.core.routing import Query
from repro.core.sharded_backend import ShardedEmbedderBackend
from repro.parallel.sharding import serve_embed_shardings

assert len(jax.devices()) == 8
cfg = get_config("bge-large-zh-v1.5").smoke()
from repro.models import embedder
params = embedder.init_embedder(jax.random.PRNGKey(0), cfg)

be = ShardedEmbedderBackend(cfg, params, max_tokens=32, dtype="bf16",
                            donate=True, async_dispatch=True,
                            min_seq_bucket=8)
assert be.device_count == 8
assert be.min_batch_bucket == 8      # batch buckets divide the mesh
# weights RESIDENT: every param leaf is fully replicated on all 8 devices
for leaf in jax.tree.leaves(be.params):
    assert len(leaf.sharding.device_set) == 8
    assert leaf.sharding.is_fully_replicated, leaf.sharding
# the batch shards over data: 8 distinct shards, one row-block each
_, bsh = serve_embed_shardings(be.mesh)
tok = jax.device_put(np.zeros((16, 32), np.int32), bsh)
assert len({s.device for s in tok.addressable_shards}) == 8
assert tok.addressable_shards[0].data.shape == (2, 32)

qs = [Query(qid=i, length=ln) for i, ln in enumerate(
    [9, 30, 22, 15, 27, 12, 18, 31, 8, 25])]
out = np.stack(be.embed_batch(qs))
ref = ShardedEmbedderBackend(cfg, params, max_tokens=32, dtype="bf16",
                             devices=jax.devices()[:1], min_seq_bucket=8)
np.testing.assert_allclose(out, np.stack(ref.embed_batch(qs)), atol=1e-5)
print("SHARDED-8DEV-OK")

# int8 weight-only serving composes with the 8-device mesh + donation +
# async dispatch: int8 leaves resident/replicated, vectors match the
# 1-device int8 mesh exactly
import jax.numpy as jnp
q8 = ShardedEmbedderBackend(cfg, params, max_tokens=32, dtype="int8",
                            donate=True, async_dispatch=True,
                            min_seq_bucket=8)
leaves = jax.tree.leaves(q8.params)
assert any(l.dtype == jnp.int8 for l in leaves)
for leaf in leaves:
    assert len(leaf.sharding.device_set) == 8
fetch = q8.embed_batch_async(qs)
out8 = np.stack(fetch())
ref8 = ShardedEmbedderBackend(cfg, params, max_tokens=32, dtype="int8",
                              devices=jax.devices()[:1], min_seq_bucket=8)
np.testing.assert_allclose(out8, np.stack(ref8.embed_batch(qs)), atol=1e-5)
print("SHARDED-8DEV-INT8-OK")

# W8A8 (int8 weights AND dynamically quantized activations) composes with
# the full mesh stack too: same int8 resident tree, act_quant switched on,
# vectors match the 1-device W8A8 mesh exactly
qaa = ShardedEmbedderBackend(cfg, params, max_tokens=32, dtype="int8_w8a8",
                             donate=True, async_dispatch=True,
                             min_seq_bucket=8)
assert qaa.act_quant and not q8.act_quant
leaves = jax.tree.leaves(qaa.params)
assert any(l.dtype == jnp.int8 for l in leaves)
for leaf in leaves:
    assert len(leaf.sharding.device_set) == 8
fetch = qaa.embed_batch_async(qs)
outaa = np.stack(fetch())
refaa = ShardedEmbedderBackend(cfg, params, max_tokens=32,
                               dtype="int8_w8a8",
                               devices=jax.devices()[:1], min_seq_bucket=8)
np.testing.assert_allclose(outaa, np.stack(refaa.embed_batch(qs)),
                           atol=1e-5)
# activation quantization actually changed the computation vs weight-only
assert float(np.abs(outaa - out8).max()) > 0.0
print("SHARDED-8DEV-W8A8-OK")
"""


def test_eight_device_mesh_end_to_end(bge_smoke):
    """Real forced 8-device host mesh (subprocess: the suite's own backend
    must keep its single device, see conftest): resident replicated weights,
    data-sharded batches, embeddings identical to the 1-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROBE],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SHARDED-8DEV-OK" in proc.stdout
    assert "SHARDED-8DEV-INT8-OK" in proc.stdout
    assert "SHARDED-8DEV-W8A8-OK" in proc.stdout


def test_serve_devices_clamps_to_pow2():
    devs = list(range(6))           # stand-in objects are fine
    assert len(_serve_devices(devs)) == 4
    assert len(_serve_devices(list(range(8)))) == 8
    with pytest.raises(ValueError):
        _serve_devices([])
