"""The engine's host spans (``windve.<tier>.<phase>``): the span tree a
profiler trace holds for a two-tier engine, on the sync and the async path,
and the ``Telemetry`` counters the same spans keep with the profiler off."""
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.routing import CPU, NPU, TierSpec
from repro.core.sharded_backend import ShardedEmbedderBackend
from repro.core.simulator import PAPER_DEVICES, ServingSimulator
from repro.core.telemetry import PHASES, HostSpans, Telemetry
from repro.core.windve import WindVE
from repro.models import embedder

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import spans as bspans  # noqa: E402

MAX_TOKENS = 32
BATCH_PHASES = ("stage", "fetch", "complete", "hooks")
PATHS = [False, True]     # the synchronous drain and the pipelined one


@pytest.fixture(scope="module")
def bge_smoke():
    cfg = get_config("bge-large-zh-v1.5").smoke()
    params = embedder.init_embedder(jax.random.PRNGKey(0), cfg)
    return cfg, params


def two_tier(cfg, params, async_dispatch):
    """An accelerator tier that fills at 8 queued queries and an offload
    tier behind it, both real sharded backends on the CPU (each compile
    lands in a ``dispatch`` span)."""
    tiers = []
    for name, depth in ((NPU, 8), (CPU, 8)):
        be = ShardedEmbedderBackend(cfg, params, max_tokens=MAX_TOKENS,
                                    async_dispatch=async_dispatch)
        tiers.append(TierSpec(name, depth, backend=be, max_batch=8))
    return WindVE(tiers=tiers)


def drive(ve, waves=4, per_wave=12):
    """Bursts of queries that overflow the accelerator tier into the
    offload tier; returns each completed query's (arrival, start, done)."""
    seen = []
    ve.add_batch_hook(lambda tier, batch, service: seen.extend(
        (q.arrival_t, q.start_t, q.done_t) for q in batch))
    rng = np.random.default_rng(0)
    for _ in range(waves):
        futs = [ve.submit(payload=rng.integers(1, 100, int(n)),
                          length=int(n))
                for n in rng.integers(4, MAX_TOKENS, per_wave)]
        for f in futs:
            if f is not None:
                f.result(timeout=60)
        time.sleep(0.03)
    return seen


def read_spans(logdir):
    """Every ``windve.`` span of the trace under ``logdir``:
    (name, start, end, batch number or None)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    assert [n for n, _, _ in bspans.XplaneSource(path).program_events()]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(bspans.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                next((v for k, v in ev.stats
                                      if k == "batch"), None)))
    return sorted(out, key=lambda x: x[1])


@pytest.fixture(scope="module", params=PATHS, ids=["sync", "async"])
def traced(request, bge_smoke, tmp_path_factory):
    """A two-tier engine served under the profiler: its spans and the
    (arrival, start, done) of every query."""
    ve = two_tier(*bge_smoke, async_dispatch=request.param)
    logdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(str(logdir), profiler_options=opts)
        try:
            seen = drive(ve)
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
    finally:
        ve.shutdown()
    return read_spans(logdir), seen


def of(spans, tier, ph):
    name = f"windve.{tier.lower()}.{ph}"
    return [s for s in spans if s[0] == name]


def test_every_phase_is_a_span(traced):
    spans, _ = traced
    names = {s[0] for s in spans}
    for tier in (NPU, CPU):
        for ph in PHASES[:-1]:
            assert f"windve.{tier.lower()}.{ph}" in names, (tier, ph)
    assert "windve.submit" in names


@pytest.mark.parametrize("parent,children", [
    ("stage", ("tokenize", "device_put", "dispatch")),
    ("fetch", ("ready", "copy"))])
def test_children_nest_in_their_batch_span(traced, parent, children):
    spans, _ = traced
    for tier in (NPU, CPU):
        parents = of(spans, tier, parent)
        numbers = [b for *_, b in parents]
        assert None not in numbers
        assert len(set(numbers)) == len(numbers)   # one span per batch
        for ph in children:
            kids = of(spans, tier, ph)
            assert kids
            for _, s, e, _ in kids:
                assert any(ps <= s and e <= pe for _, ps, pe, _ in parents), \
                    (tier, ph, s, e)


def test_stage_and_fetch_carry_the_same_batches(traced):
    spans, _ = traced
    for tier in (NPU, CPU):
        staged = sorted(b for *_, b in of(spans, tier, "stage"))
        assert staged == sorted(b for *_, b in of(spans, tier, "fetch"))
        assert staged == sorted(b for *_, b in of(spans, tier, "complete"))


def test_worker_spans_tile_its_thread(traced):
    spans, _ = traced
    from bench.trace import union_length

    top = [(s, e) for n, s, e, _ in spans
           if n.startswith("windve.npu.") and bspans.phase(n) in bspans.WORKER]
    t0 = of(spans, NPU, "complete")[0][2]          # after the first batch
    t1 = max(e for _, e in top)
    covered = union_length((max(s, t0), e) for s, e in top if e > t0)
    assert covered >= 0.95 * (t1 - t0)


def test_start_t_lies_between_arrival_and_done(traced):
    _, seen = traced
    assert seen
    for arrival, start, done in seen:
        assert arrival <= start <= done


@pytest.mark.parametrize("async_dispatch", PATHS, ids=["sync", "async"])
def test_counters_count_one_per_batch(bge_smoke, async_dispatch):
    ve = two_tier(*bge_smoke, async_dispatch=async_dispatch)
    try:
        drive(ve, waves=2)
    finally:
        ve.shutdown()
    s = ve.stats
    for tier in (NPU, CPU):
        batches = len(s.tier_batch_latencies[tier])
        totals = s.spans(tier).totals()
        assert batches and s.spans(tier).batches == batches
        for ph in BATCH_PHASES:
            assert totals[ph][0] == batches, (tier, ph)
        assert totals["tokenize"][0] >= batches
    summary = s.summary()
    assert summary["host_ms_stage_NPU"] > 0
    assert summary["host_ms_copy_CPU"] >= 0
    assert summary["host_us_submit"] > 0


def test_counters_lose_no_update_across_threads():
    spans = HostSpans("npu")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(500):
                with spans.span("pop"):
                    pass
                spans.next_batch()

        threads = [threading.Thread(target=work)
                   for _ in range(2 * (os.cpu_count() or 1))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert spans.totals()["pop"][0] == 500 * len(threads)
    assert spans.batches == 500 * len(threads)


def test_names_are_built_per_tier():
    assert HostSpans("NPU@h0r1").names["stage"] == "windve.npu@h0r1.stage"
    assert HostSpans("").names["submit"] == "windve.submit"


def test_the_des_keeps_no_host_phases():
    sim = ServingSimulator(PAPER_DEVICES["tesla-v100/bge"], None, 8, 0)
    s = sim.run_burst(20)
    assert not any(k.startswith("host_") for k in s.summary())
    assert not any(k.startswith("host_") for k in Telemetry().summary())
