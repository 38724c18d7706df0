"""The accelerator tier's pipelined drain: the engine worker enqueues batch
N+1 before it fetches batch N, times each batch's own service, and counts
the batches it enqueued with one in flight; the Eq. 12 probe drains the
same way.  A fake device (one thread running enqueued batches in order)
stands in for the chip."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.routing import NPU, Query, TierSpec
from repro.core.sharded_backend import ShardedEmbedderBackend
from repro.core.windve import Backend, WindVE
from repro.launch import serve
from repro.models import embedder

DIM = 4


def answer(q):
    """The fake model: a query's answer is its payload id, broadcast."""
    return np.full(DIM, float(q.payload[0]), np.float32)


class FakeDevice(Backend):
    """Batches run one after another on a device thread, ``device_s``
    each; an enqueue stages for ``stage_s`` on the caller's thread and the
    fetch thunk blocks on the batch's completion event.  The first enqueue
    waits for ``loaded``, so a test can fill the queue before the worker's
    second pop.  ``log`` records ("enqueue", n) and ("fetch", n) in the
    order they happen; ``in_flight_at_enqueue`` how many of the batches
    enqueued before each one were not yet fetched."""

    def __init__(self, pipelined, device_s=0.0, stage_s=0.0):
        self.async_dispatch = pipelined
        self.device_s, self.stage_s = device_s, stage_s
        self.loaded = threading.Event()
        self.log, self.in_flight_at_enqueue = [], []
        self.fetch_calls = {}
        self._lock = threading.Lock()
        self._n = 0
        self._unfetched = 0
        self._device_free = 0.0     # monotonic time the device next idles

    def embed_batch_async(self, queries):
        self.loaded.wait(timeout=30)
        time.sleep(self.stage_s)
        with self._lock:
            self._n += 1
            n = self._n
            self.log.append(("enqueue", n))
            self.in_flight_at_enqueue.append(self._unfetched)
            self._unfetched += 1
            self.fetch_calls[n] = 0
            start = max(time.monotonic(), self._device_free)
            self._device_free = end = start + self.device_s
        done = threading.Event()
        threading.Timer(max(0.0, end - time.monotonic()), done.set).start()
        out = [answer(q) for q in queries]

        def fetch():
            with self._lock:
                self.log.append(("fetch", n))
                self.fetch_calls[n] += 1
            done.wait(timeout=30)
            with self._lock:
                self._unfetched -= 1
            return out

        return fetch

    def embed_batch(self, queries):
        return self.embed_batch_async(queries)()


def serve_all(be, n_queries, max_batch=2):
    """Fill one tier's queue with ``n_queries`` (the first enqueue held
    until all are in), then collect every future's answer and every
    batch's (qids, service) from the batch hook."""
    ve = WindVE(tiers=[TierSpec(NPU, 1000, backend=be, max_batch=max_batch)])
    seen = []
    ve.add_batch_hook(lambda tier, batch, service: seen.append(
        ([q.qid for q in batch], service)))
    try:
        futs = [ve.submit(payload=np.array([i + 1]), length=1)
                for i in range(n_queries)]
        be.loaded.set()
        got = [f.result(timeout=30) for f in futs]
        deadline = time.monotonic() + 5
        while len(seen) < len(be.fetch_calls) and time.monotonic() < deadline:
            time.sleep(0.01)         # the last batch's hook runs after its
    finally:                         # futures resolve
        ve.shutdown()
    return ve, got, seen


def test_next_batch_is_enqueued_before_the_previous_fetch():
    be = FakeDevice(pipelined=True, device_s=0.01)
    _, got, seen = serve_all(be, 12)
    n = len(be.fetch_calls)
    assert n == 6
    want = [("enqueue", 1)]
    for k in range(2, n + 1):
        want += [("enqueue", k), ("fetch", k - 1)]
    assert be.log == want + [("fetch", n)]
    # every future gets its own answer, batch by batch in queue order, the
    # same as the synchronous drain serves
    sync = FakeDevice(pipelined=False, device_s=0.01)
    _, sync_got, sync_seen = serve_all(sync, 12)
    assert sync.log == [(kind, k) for k in range(1, 7)
                        for kind in ("enqueue", "fetch")]
    for a, b, i in zip(got, sync_got, range(12)):
        np.testing.assert_array_equal(a, np.full(DIM, i + 1.0))
        np.testing.assert_array_equal(a, b)
    assert [qids for qids, _ in seen] == [qids for qids, _ in sync_seen] \
        == [[2 * k + 1, 2 * k + 2] for k in range(6)]


DEVICE_S, STAGE_S = 0.3, 0.12


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "sync"])
def test_service_is_one_batchs_own(pipelined):
    """Pipelined, each batch after the first waits on the device behind
    its predecessor and overlaps the next batch's staging; its service is
    still about one device run, not two, and not staging plus device.
    Synchronous, service is pop to results: staging plus device."""
    be = FakeDevice(pipelined, device_s=DEVICE_S, stage_s=STAGE_S)
    _, _, seen = serve_all(be, 10)
    services = [s for _, s in seen]
    assert len(services) == 5
    if pipelined:
        for s in services[1:]:
            assert DEVICE_S - 0.05 < s < DEVICE_S + STAGE_S / 2, services
    else:
        for s in services[1:]:
            assert DEVICE_S + STAGE_S - 0.02 < s < DEVICE_S + STAGE_S + 0.08, \
                services


def test_overlapped_batches_counts_those_enqueued_with_one_in_flight():
    be = FakeDevice(pipelined=True, device_s=0.005)
    ve = WindVE(tiers=[TierSpec(NPU, 1000, backend=be, max_batch=2)])
    try:
        for wave in range(2):        # the queue empties between waves
            futs = [ve.submit(payload=np.array([i + 1]), length=1)
                    for i in range(8)]
            be.loaded.set()
            for f in futs:
                f.result(timeout=30)
            time.sleep(0.05)
    finally:
        ve.shutdown()
    s = ve.stats.summary()
    want = sum(1 for k in be.in_flight_at_enqueue if k)
    assert max(be.in_flight_at_enqueue) == 1       # never two in flight
    assert s["overlapped_batches_NPU"] == want >= 3
    assert s["batches_NPU"] == len(be.fetch_calls)
    assert want < s["batches_NPU"]   # a wave's first batch has none before

    sync = FakeDevice(pipelined=False, device_s=0.005)
    sync_ve, _, _ = serve_all(sync, 8)
    s = sync_ve.stats.summary()
    assert s["overlapped_batches_NPU"] == 0 and s["batches_NPU"] == 4


def test_profile_fn_drains_like_the_pipelined_worker():
    be = FakeDevice(pipelined=True, device_s=0.002)
    be.loaded.set()
    prof = serve.profile_fn(be, vocab=100, max_batch=4)
    t = prof(10)          # three chunks: a warm pass, then two timed ones
    assert np.isfinite(t) and t > 0
    assert len(be.fetch_calls) == 9
    assert set(be.fetch_calls.values()) == {1}      # each thunk once
    assert be.log[:6] == [("enqueue", 1), ("fetch", 1), ("enqueue", 2),
                          ("fetch", 2), ("enqueue", 3), ("fetch", 3)]
    for a in (4, 7):      # each timed pass: chunk k+1 out before k's fetch
        k = 2 * a - 2
        assert be.log[k:k + 6] == [
            ("enqueue", a), ("enqueue", a + 1), ("fetch", a),
            ("enqueue", a + 2), ("fetch", a + 1), ("fetch", a + 2)]


def test_profile_fn_of_a_sync_backend_is_unchanged():
    be = FakeDevice(pipelined=False, device_s=0.002)
    be.loaded.set()
    t = serve.profile_fn(be, vocab=100, max_batch=4)(10)
    assert np.isfinite(t) and t > 0
    assert be.log == [(kind, k) for k in range(1, 10)
                      for kind in ("enqueue", "fetch")]


# ------------------------------------------------- the real backend --
MAX_TOKENS = 32


@pytest.fixture(scope="module")
def bge_smoke():
    cfg = get_config("bge-large-zh-v1.5").smoke()
    params = embedder.init_embedder(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_cpu_mesh_drains_synchronously_unless_pinned(bge_smoke):
    cfg, params = bge_smoke
    auto = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS)
    assert auto.platform == "cpu" and not auto.async_dispatch
    pinned = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    async_dispatch=True)
    assert pinned.async_dispatch
    rng = np.random.default_rng(3)
    payloads = [rng.integers(1, cfg.vocab_size, 12) for _ in range(24)]
    out = {}
    for name, be in (("auto", auto), ("pinned", pinned)):
        ve = WindVE(tiers=[TierSpec(NPU, 64, backend=be, max_batch=4)])
        try:
            futs = [ve.submit(payload=p, length=len(p)) for p in payloads]
            out[name] = np.stack([f.result(timeout=60) for f in futs])
        finally:
            ve.shutdown()
        out[name + "_overlapped"] = \
            ve.stats.summary()["overlapped_batches_NPU"]
    assert out["auto_overlapped"] == 0
    assert out["pinned_overlapped"] > 0
    np.testing.assert_allclose(out["auto"], out["pinned"], atol=1e-5)


def test_200_batches_on_one_bucket_never_overrun_the_staging_ring(
        bge_smoke):
    """The pipelined worker holds at most two batches of a bucket staged
    and unfetched, inside the default ring of four: 200 back-to-back
    (2, 16) batches raise no overrun and serve every query its own
    answer."""
    cfg, params = bge_smoke
    be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                min_seq_bucket=16, async_dispatch=True)
    rng = np.random.default_rng(5)
    payloads = [rng.integers(1, cfg.vocab_size, 10) for _ in range(400)]
    ve = WindVE(tiers=[TierSpec(NPU, 1000, backend=be, max_batch=2)])
    try:
        futs = [ve.submit(payload=p, length=len(p)) for p in payloads]
        got = [f.result(timeout=120) for f in futs]
    finally:
        ve.shutdown()
    s = ve.stats.summary()
    assert s["batches_NPU"] >= 200 and s["overlapped_batches_NPU"] > 0
    assert not ve.stats.backend_errors and ve.stats.failed == 0
    assert set(be._staging) == {(2, 16)}
    assert not be._staging_pending            # every fetch came back
    oracle = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    min_seq_bucket=16)
    for i in (0, 1, 199, 398, 399):
        want = oracle.embed_batch([Query(qid=i, payload=payloads[i],
                                         length=10)])[0]
        np.testing.assert_allclose(got[i], want, atol=1e-5)
