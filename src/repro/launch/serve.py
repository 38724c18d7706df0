"""Serving driver: the full WindVE pipeline on this host.

Device detector (the paper's Algorithm 2) -> one real embedder backend per
tier, both ``ShardedEmbedderBackend``s in this one process: the
accelerator tier on the TPU chips and the offload tier on the host CPU ->
queue-depth calibration (an Eq. 12 sweep of each tier's own backend) ->
queue manager -> threaded engine -> workload replay -> stats::

    PYTHONPATH=src python -m repro.launch.serve --queries 64 --slo 1.0 \
        --opt embed_dtype=bf16,embed_donate=1 --prewarm

The model serves at its published width; ``--smoke`` swaps in the narrow
``.smoke()`` config for a CPU rehearsal (``JAX_PLATFORMS=cpu``), where
Algorithm 2 finds no accelerator and serves from the CPU alone
(``main=cpu heter=False``).  ``npu_model=`` (``--npu-model``) replaces the
accelerator tier with a modeled device from ``simulator.PAPER_DEVICES``
for the DES and for tests; it is used only when named.

Each tier's kernels follow its devices: compiled Pallas kernels on the TPU
tier, their jnp references on the CPU tier (``repro.kernels.placement``).
``embed_dtype=int8`` serves the weight-only quantized trunk (int8
projections + fp32 dequant scales via the fused quant matmul, 4x smaller
resident weights, >= 0.99 cosine vs the fp32 oracle); ``int8_w8a8`` also
quantizes the activations per batch (int8 x int8 projections with int32
accumulation, >= 0.98 cosine).  With ``--policy length-aware`` the offload
threshold is calibrated from one Eq. 12 fit PER seq-length bucket of the
CPU tier (see ``estimator.quantized_fit``).
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import sys
import time
from typing import Callable, Optional

import jax

from repro import perf_flags
from repro.configs import get_config
from repro.core import adaptive
from repro.core.admission import AdmissionController
from repro.core.bucketing import length_bucket_fn
from repro.core.cache import cache_tier
from repro.core.device_detector import detect, probe_jax_devices
from repro.core.estimator import (UNBOUNDED_DEPTH, estimate_depth_per_bucket,
                                  fanout_probe_points, fit_latency,
                                  replica_fits)
from repro.core.health import BrownoutController, CircuitBreaker
from repro.core.routing import (CPU, NPU, CascadePolicy, LeastLoadedPolicy,
                                LengthAwarePolicy, PredictivePolicy, Query,
                                RetryPolicy, RoundRobinPolicy, TierSpec,
                                replicate)
from repro.core.sharded_backend import ShardedEmbedderBackend, _serve_devices
from repro.core.simulator import PAPER_DEVICES, profile_fn_for
from repro.core.telemetry import PHASES
from repro.core.windve import ModeledBackend, WindVE, pipelines
from repro.data.workload import make_queries
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import replica_groups
from repro.models import embedder

POLICIES = {
    "cascade": CascadePolicy,
    "length-aware": LengthAwarePolicy,
    "least-loaded": LeastLoadedPolicy,
    "predictive": PredictivePolicy,
    "round-robin": RoundRobinPolicy,
}

MIN_SEQ_BUCKET = 16
QUERY_LENGTH = 75        # the paper's query length
# rows one execution serves, and so the top of the prewarmed (B, S) grid:
# an accelerator wants wide batches; on the host CPU service time grows
# with every row anyway, so narrow batches cost nothing and keep the grid
# (and its compile time) small
ACCEL_MAX_BATCH = 64
CPU_MAX_BATCH = 8
# ... and tokens one execution serves: 64 rows of bge-large's 96, or 8 of
# ModernBERT-large's 8,192 (its GeGLU intermediate is then 0.7 GB in bf16)
TOKEN_BUDGET = 65536
# the offload tier's Eq. 11 check probes its smallest batch from inputs
# this long, doubling up to the calibration length: a tier that misses the
# SLO on a prefix of the input misses it on the whole input, and one long
# input on the host CPU can take minutes
CHECK_FROM_TOKENS = 128
# calibration re-probes an extrapolated depth: each round probes 5% past
# the fitted depth, never more than 4x the largest point measured so far
REPROBE_PAST = 1.05
REPROBE_REACH = 4
REPROBE_ROUNDS = 4


def max_batch_for(platform: str, max_tokens: int) -> int:
    """Rows one execution of a tier on ``platform`` serves, for inputs of
    up to ``max_tokens`` tokens."""
    rows = CPU_MAX_BATCH if platform == "cpu" else ACCEL_MAX_BATCH
    return max(1, min(rows, TOKEN_BUDGET // max_tokens))


def profile_fn(backend, vocab: int, max_batch: int, seed: int = 0,
               length: int = QUERY_LENGTH) -> Callable[..., float]:
    """Eq. 12 probe of a real backend: the seconds it takes to serve ``c``
    queued queries of ``length`` tokens in batches of at most
    ``max_batch``, the way the engine's worker drains its queue: on a
    pipelined backend (``windve.pipelines``) chunk k+1 is enqueued before
    chunk k is fetched, on any other chunk by chunk.  Every shape is run
    once before the clock starts, so a probe times service and never
    compilation; the best of ``passes`` timed passes is returned."""
    pipelined = pipelines(backend)

    def drain(chunks) -> None:
        if not pipelined:
            for chunk in chunks:
                backend.embed_batch(chunk)
            return
        fetch = None
        for chunk in chunks:
            nxt = backend.embed_batch_async(chunk)
            if fetch is not None:
                fetch()
            fetch = nxt
        fetch()

    def profile(c: int, length: int = length, passes: int = 2) -> float:
        qs = [Query(qid=i, payload=p, length=length) for i, p in
              enumerate(make_queries(c, vocab, length=length, seed=seed))]
        chunks = [qs[i:i + max_batch] for i in range(0, c, max_batch)]
        for chunk in chunks:             # compile before timing
            backend.embed_batch(chunk)
        best = float("inf")
        for _ in range(passes):
            t0 = time.monotonic()
            drain(chunks)
            best = min(best, time.monotonic() - t0)
        return best

    return profile


def probe_points(max_batch: int, floor: int = 1) -> tuple:
    """Queue lengths to probe: from a quarter of a batch to two batches, so
    service time grows across the points (a partial batch costs less than a
    full one, two batches cost two executions).  ``floor``: the backend's
    smallest batch bucket, below which every probe runs one shape."""
    return tuple(max(floor, max_batch * k // 4) for k in (1, 2, 4, 8))


def slo_check(profile: Callable[..., float], slo: float, length: int):
    """``check(c) -> (seconds, tokens)``: ``c`` queries timed once each (after
    their compile) at inputs of ``CHECK_FROM_TOKENS`` tokens, doubling up
    to ``length`` (or at ``length`` alone, if that is shorter), stopping at
    the first length that misses ``slo``."""

    def check(c: int):
        n = min(length, CHECK_FROM_TOKENS)
        while True:
            t = profile(c, n, passes=1)
            if t > slo or n >= length:
                return t, n
            n = min(2 * n, length)

    return check


def calibrate(name: str, profile: Callable[[int], float], slo: float,
              points: tuple, check: Optional[Callable] = None):
    """Eq. 12 depth of one tier, read inside the range it was measured on.

    ``check`` (``slo_check``), when given, first times the smallest probe
    batch alone: if it misses the SLO, the tier's depth is 0 (Eq. 11) and
    the fit None, with no sweep.

    A flat fit (no growth across the probe points) yields the estimator's
    unbounded sentinel: that is a broken measurement, and serving it would
    admit without limit.  A depth past the largest probe point is an
    extrapolation, and service time may bend upward out there: the queue
    is probed again just past that depth (at most ``REPROBE_REACH`` times
    the largest point so far) and the line refit over every point, until
    the depth lies within the probed range."""
    if check is not None:
        t, n = check(points[0])
        if t > slo:
            print(f"[serve] {name}: {points[0]} queries of {n} tokens took "
                  f"{t:.2f}s, past the {slo}s SLO: depth 0 (Eq. 11)")
            return 0, None
    measured = {}
    for _ in range(REPROBE_ROUNDS + 1):
        for c in points:
            if c not in measured:
                measured[c] = profile(c)
        fit = fit_latency(list(measured), list(measured.values()))
        depth, top = fit.max_concurrency(slo), max(measured)
        if depth >= UNBOUNDED_DEPTH:
            raise RuntimeError(
                f"{name}: the Eq. 12 fit over probe points "
                f"{sorted(measured)} is flat (alpha={fit.alpha:.3g} "
                f"s/query, beta={fit.beta:.4f} s); its depth is unbounded, "
                f"so the tier cannot be calibrated")
        if depth <= top:
            return depth, fit
        points = (min(math.ceil(depth * REPROBE_PAST), REPROBE_REACH * top),)
    raise RuntimeError(
        f"{name}: the Eq. 12 depth {depth} still lies past the largest "
        f"probe point {top} after {REPROBE_ROUNDS} re-probes "
        f"(points {sorted(measured)})")


def build_engine(model: str = "bge-large-zh-v1.5", slo: float = 1.0,
                 smoke: bool = False, heter: bool = True,
                 npu_model: Optional[str] = None, seed: int = 0,
                 policy: str = "cascade", devices: int = 0,
                 npu_devices: int = 1, prewarm: bool = False,
                 hosts: int = 1, replicas: int = 1):
    cfg = get_config(model)
    if smoke:
        cfg = cfg.smoke()
    params = embedder.init_embedder(jax.random.PRNGKey(seed), cfg)

    inv = probe_jax_devices()
    if npu_model is not None:
        # a named, modeled accelerator stands in for the chip
        inv = dataclasses.replace(inv, npus=max(inv.npus, 1))
    det = detect(inv, heter_requested=heter)
    print(f"[serve] detector: main={det.device_main} aux={det.device_auxiliary} "
          f"heter={det.heter_enable}")
    if det.device_main == "none":
        raise RuntimeError("no device to serve from")
    bucket_fn = length_bucket_fn(MIN_SEQ_BUCKET, cfg.max_tokens)

    def max_batch(be) -> int:
        return max_batch_for(be.platform, cfg.max_tokens)

    def real_backend(devs) -> ShardedEmbedderBackend:
        be = ShardedEmbedderBackend(cfg, params, max_tokens=cfg.max_tokens,
                                    devices=devs,
                                    min_seq_bucket=MIN_SEQ_BUCKET)
        print(f"[serve] backend {be.name}: {be.device_count} "
              f"{be.platform} device(s), batches <= {max_batch(be)}")
        return be

    def warm(be) -> None:
        if prewarm:
            n = be.prewarm(be.warm_grid(max_batch=max_batch(be)))
            print(f"[serve] prewarmed {n} (B, S) buckets of {be.name}")

    def calibrate_real(name: str, be: ShardedEmbedderBackend,
                       offload: bool = False):
        prof = profile_fn(be, cfg.vocab_size, max_batch(be), seed,
                          length=cfg.probe_tokens)
        points = probe_points(max_batch(be), be.min_batch_bucket)
        if offload:
            depth, fit = calibrate(name, prof, slo, points,
                                   check=slo_check(prof, slo,
                                                   cfg.probe_tokens))
        else:
            depth, fit = calibrate(name, prof, slo, points)
        return depth, fit, prof

    # the main tier, expanded to hosts x replicas first-class tiers
    # (replicate(spec, 1, 1) returns the original spec untouched): each
    # replica gets its own backend — and below its own breaker, Eq. 12 fit
    # and admission watermark, because a replica is an independently-
    # failing capacity unit
    main = NPU if det.device_main == "npu" else CPU
    if npu_model is not None:
        model_dev = PAPER_DEVICES[npu_model]

        def main_backend(h: int, r: int) -> ModeledBackend:
            return ModeledBackend(model_dev, embed_dim=cfg.d_model,
                                  devices=npu_devices)

        main_be = main_backend(0, 0)
        d_main, fit_main = calibrate(
            main, profile_fn_for(main_be.model), slo,
            fanout_probe_points(npu_devices))
        spec_kw = {}
    else:
        pool = inv.npu_devices if main == NPU else inv.cpu_devices
        pool = _serve_devices(pool[:devices] if devices else pool)
        main_backends = [real_backend(g)
                         for g in replica_groups(hosts, replicas, pool)]
        for be in main_backends:
            warm(be)
        main_be = main_backends[0]

        def main_backend(h: int, r: int) -> ShardedEmbedderBackend:
            return main_backends[h * replicas + r]

        d_main, fit_main, _ = calibrate_real(main, main_be)
        spec_kw = dict(max_batch=max_batch(main_be), bucket_fn=bucket_fn)
    d_main = max(d_main, 1)
    main_spec = TierSpec(main, d_main, backend=main_be, **spec_kw)

    d_cpu, fit_c, cpu_be = 0, None, None
    if det.heter_enable:
        cpu_be = real_backend(list(inv.cpu_devices))
        d_cpu, fit_c, cpu_profile = calibrate_real(CPU, cpu_be, offload=True)
        if d_cpu > 0:
            warm(cpu_be)
        else:               # the offload tier is left out of the engine
            cpu_be = None
    print(f"[serve] depths: C_{main}={d_main} "
          f"(a={fit_main.alpha:.5f} b={fit_main.beta:.4f})"
          + (f" C_CPU={d_cpu} (a={fit_c.alpha:.5f} b={fit_c.beta:.4f})"
             if fit_c else "")
          + (" C_CPU=0 (its smallest probe batch misses the SLO)"
             if det.heter_enable and not fit_c else ""))

    main_tiers = replicate(main_spec, hosts, replicas, backend=main_backend)
    if len(main_tiers) > 1:
        print(f"[serve] replicas: {hosts} host(s) x {replicas} = "
              f"{len(main_tiers)} {main} replica tier(s), "
              f"C_total={d_main * len(main_tiers)}: "
              + " ".join(t.name for t in main_tiers))
    # per-replica Eq. 12 fits, keyed by replica tier name — what makes the
    # predictive policy and the admission controller price each replica's
    # backlog against its own service curve
    if npu_model is not None:
        main_fits = replica_fits(
            {t.name: t.backend.model for t in main_tiers},
            probe_points=fanout_probe_points(npu_devices))
    else:
        main_fits = {t.name: fit_main for t in main_tiers}
    fits = {**main_fits, **({CPU: fit_c} if fit_c else {})}

    policy_obj = POLICIES[policy]()
    if policy == "predictive":
        # seed the latency-predictive dispatch with the offline Eq. 12 fits
        # (per-tier service curves); the online calibrator attached below
        # refreshes them from live traffic through the batch hook
        policy_obj = PredictivePolicy(fits=fits, bucket_fn=bucket_fn)
    if policy == "length-aware" and d_cpu > 0:
        # one Eq. 12 fit PER seq-length bucket of the offload tier: the
        # long-query threshold is the first bucket whose measured CPU depth
        # collapses to 0, so the policy follows the bucketed (and, under
        # embed_dtype=int8, quantized) service curve instead of a
        # hand-picked default
        s, lengths = MIN_SEQ_BUCKET, []
        while s < cfg.max_tokens:
            lengths.append(s)
            s *= 2
        lengths.append(cfg.max_tokens)
        bucket_fits = estimate_depth_per_bucket(
            cpu_profile, slo, lengths,
            probe_points=probe_points(max_batch(cpu_be),
                                      cpu_be.min_batch_bucket)[:3])
        policy_obj = LengthAwarePolicy.from_bucket_depths(
            {b: d for b, (d, _) in bucket_fits.items()})
        print("[serve] per-bucket depths: "
              + " ".join(f"S{b}:C={d}"
                         for b, (d, _) in sorted(bucket_fits.items()))
              + f" -> long_threshold={policy_obj.long_threshold}")

    # the topology is a TierSpec list: N tiers are a config change, not a
    # rewrite (e.g. append a little-core CPU pool here)
    tiers = list(main_tiers)
    if d_cpu > 0:
        tiers.append(TierSpec(CPU, d_cpu, backend=cpu_be,
                              max_batch=max_batch(cpu_be),
                              bucket_fn=bucket_fn))
    # --opt cache=N[,cache_bytes=M]: the zero-cost tier at the head of the
    # topology — exact-match hits bypass every device queue entirely
    flags = perf_flags.FLAGS
    if flags.cache > 0:
        tiers.insert(0, cache_tier(flags.cache,
                                   flags.cache_bytes or None))
        print(f"[serve] cache tier: {flags.cache} entries"
              + (f", {flags.cache_bytes} bytes" if flags.cache_bytes else "")
              + " (exact-match LRU at the head of the topology)")
    # --opt breaker=N[,breaker_cooldown_ms=M]: per-tier circuit breakers —
    # N consecutive batch failures trip a tier out of dispatch until its
    # half-open probe recovers; every policy routes around it transparently
    if flags.breaker > 0:
        for t in tiers:
            if t.cache is None:
                t.breaker = CircuitBreaker(
                    failure_threshold=flags.breaker,
                    cooldown_s=flags.breaker_cooldown_ms / 1e3)
        print(f"[serve] breakers: trip after {flags.breaker} consecutive "
              f"failures, cooldown {flags.breaker_cooldown_ms}ms")
    # --opt retries=N[,retry_backoff_ms=M] + deadline_ms=D: failed batches
    # re-dispatch through the policy path; overdue queued queries expire
    retry = RetryPolicy(max_retries=flags.retries,
                        backoff_s=flags.retry_backoff_ms / 1e3)
    deadline_s = flags.deadline_ms / 1e3 if flags.deadline_ms > 0 else None
    if flags.retries or deadline_s is not None:
        print(f"[serve] fault tolerance: retries={flags.retries} "
              f"backoff={flags.retry_backoff_ms}ms "
              f"deadline={flags.deadline_ms or 'none'}ms")
    # --opt admission=on[,reject_cost=X,watermark=N] + brownout=on: the
    # overload-control pair.  Quantized serving paths mark their tier so
    # brownout degradation can prefer them at equal backlog.
    for t in tiers:
        if getattr(t.backend, "dtype", None) in ("int8", "int8_w8a8"):
            t.quantized = True
    admission = None
    if flags.admission:
        admission = AdmissionController(
            fits=fits,
            slo_s=slo, reject_cost=flags.reject_cost,
            watermark=flags.watermark)
        print(f"[serve] admission control: reject_cost={flags.reject_cost} "
              f"watermark={flags.watermark} "
              f"(priced against the calibrated Eq. 12 fits)")
    brownout = None
    if flags.brownout:
        brownout = BrownoutController()
        print(f"[serve] brownout: degraded@{brownout.degraded_at} "
              f"shedding@{brownout.shedding_at} "
              f"deadline_scale={brownout.deadline_scale}")
    engine = WindVE(tiers=tiers, policy=policy_obj, retry=retry,
                    default_deadline_s=deadline_s,
                    admission=admission, brownout=brownout)
    if policy == "predictive":
        # live fits: every completed batch feeds the calibrator; every refit
        # streams fresh per-tier (and per-bucket) curves into the policy
        adaptive.attach(engine, adaptive.OnlineCalibrator(slo),
                        policy=policy_obj, bucket_fn=bucket_fn)
    return engine, cfg


def serve_burst(engine, queries, length: int, timeout_s: float = 60.0):
    """Submit every query at once and wait for the accepted ones.

    Returns ``(results, failures, rejected, wall_s)``: ``results`` holds one
    entry per accepted query in submission order (the embedding, or None
    where it failed), ``failures`` the exceptions of accepted queries that
    did not complete, ``rejected`` how many the engine turned away (BUSY).
    """
    t0 = time.monotonic()
    futs = [engine.submit(payload=q, length=length) for q in queries]
    results, failures = [], []
    for f in futs:
        if f is None:
            continue
        try:
            results.append(f.result(timeout=timeout_s))
        except Exception as e:       # ServeError / DeadlineExceeded / timeout
            results.append(None)
            failures.append(e)
    return (results, failures, sum(f is None for f in futs),
            time.monotonic() - t0)


def report(engine, n_queries: int, wall: float, completed: int,
           failed: int, slo: float) -> None:
    """The serve summary: admission, faults, latency, per-tier batches and
    each real backend's retrace counter."""
    s = engine.stats
    print(f"[serve] {n_queries} queries in {wall:.2f}s: "
          f"accepted={s.accepted} rejected(BUSY)={s.rejected} "
          f"completed={completed} failed={failed}")
    if any(s.rejections.values()) or s.brownout_transitions:
        rej = " ".join(f"{k}={v}" for k, v in sorted(s.rejections.items())
                       if v)
        bro = " ".join(f"->{k}x{v}" for k, v in
                       sorted(s.brownout_transitions.items()))
        print(f"[serve] overload: rejections {rej or 'none'}"
              + (f"  brownout {bro}" if bro else ""))
    if failed or s.deadline_misses or s.backend_errors or s.retries:
        print(f"[serve] faults: deadline_misses="
              f"{sum(s.deadline_misses.values())} "
              f"retries={sum(s.retries.values())} "
              f"backend_errors={sum(s.backend_errors.values())} "
              f"breaker trips={sum(s.breaker_trips.values())} "
              f"recoveries={sum(s.breaker_recoveries.values())}")
    print(f"[serve] per-device: {s.per_device}  "
          f"p50={s.p(50):.3f}s p99={s.p(99):.3f}s  "
          f"SLO({slo}s) violations="
          f"{sum(1 for l in s.latencies if l > slo)}")
    # replica-aware summary: per-replica counters rolled up by logical
    # tier, so imbalance (and a quarantined replica) is visible at a
    # glance instead of buried in @hXrY-keyed raw counters
    for base, g in sorted(s.replica_rollup().items()):
        if len(g["replicas"]) < 2:
            continue
        split = g.get("dispatched_by_replica", {})
        print(f"[serve] replicas[{base}]: dispatched="
              f"{g.get('dispatched', 0)} completed="
              f"{g.get('completed', 0)} over {len(g['replicas'])} "
              f"replicas  ["
              + " ".join(f"{n}={split.get(n, 0)}"
                         for n in g["replicas"]) + "]")
    tails = "  ".join(
        f"{t}: p95={s.batch_p(95, t)*1e3:.1f}ms"
        for t in sorted(s.tier_batch_latencies))
    print(f"[serve] batch service tail: p50={s.batch_p(50)*1e3:.1f}ms "
          f"p95={s.batch_p(95)*1e3:.1f}ms p99={s.batch_p(99)*1e3:.1f}ms "
          f"over {len(s.batch_latencies)} batches  [{tails}]")
    # host time per batch by phase (``windve.<tier>.<phase>`` spans): what
    # the worker did besides waiting on the device; ``overlapped``: the
    # batches enqueued while the worker had one in flight (pipelined drain)
    for tier, phases in s.host_ms_per_batch().items():
        print(f"[serve] host ms/batch {tier}: " + " ".join(
            f"{p}={phases[p]:.3f}" for p in PHASES if p in phases)
            + f"  overlapped={s.overlapped_batches.get(tier, 0)}"
            f"/{s.spans(tier).batches}")
    submit_us = s.host_us_per_submit()
    if submit_us is not None:
        print(f"[serve] host submit: {submit_us:.1f}us/query")
    traces = {name: be.traces for name, be in engine.backends.items()
              if hasattr(be, "traces")}
    if traces:
        print("[serve] traces: " + " ".join(f"{n}={t}" for n, t in
                                            sorted(traces.items())))
    if s.cache_hits or s.cache_misses:
        print(f"[serve] cache: hit-rate={s.cache_hit_rate():.1%} "
              f"hits={sum(s.cache_hits.values())} "
              f"misses={sum(s.cache_misses.values())} "
              f"inserts={sum(s.cache_inserts.values())} "
              f"evictions={sum(s.cache_evictions.values())} "
              f"staleness p50={s.cache_staleness(50):.2f}s")
    print(f"[serve] max concurrency C = {engine.max_concurrency}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="bge-large-zh-v1.5")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the narrow .smoke() config (CPU rehearsal) "
                         "instead of the published width")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--slo", type=float, default=1.0)
    ap.add_argument("--length", type=int, default=QUERY_LENGTH)
    ap.add_argument("--no-heter", action="store_true",
                    help="disable CPU offloading (the paper's baseline)")
    ap.add_argument("--policy", default="cascade", choices=sorted(POLICIES),
                    help="dispatch policy (cascade == paper Algorithm 1)")
    ap.add_argument("--opt", default="",
                    help="perf flags, e.g. embed_dtype=int8_w8a8,"
                         "cache=4096,cache_bytes=0 "
                         "(embed_dtype: fp32|bf16|int8|int8_w8a8; cache=N "
                         "puts an N-entry exact-match embedding cache at "
                         "the head of the dispatch topology); fault "
                         "tolerance: deadline_ms=N,retries=N,"
                         "retry_backoff_ms=N,breaker=N,breaker_cooldown_ms=N"
                         "; overload control: admission=on,reject_cost=X,"
                         "watermark=N,brownout=on")
    ap.add_argument("--devices", type=int, default=0,
                    help="devices the main tier fans out over (0 = all)")
    ap.add_argument("--npu-model", default=None,
                    choices=sorted(PAPER_DEVICES),
                    help="serve the accelerator tier from this modeled "
                         "device (DES calibration) instead of the chip")
    ap.add_argument("--npu-devices", type=int, default=1,
                    help="devices the MODELED accelerator tier fans out "
                         "over (DES-calibrated Eq. 12 fan-out curve)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="hosts the main tier replicates across; "
                         "each host carries --replicas replica tiers "
                         "(1x1 = the single-replica path)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="main-tier replicas per host — each an "
                         "independently-failing tier with its own devices, "
                         "queue, breaker, and Eq. 12 fit")
    ap.add_argument("--prewarm", action="store_true",
                    help="compile the (B, S) bucket grid before serving")
    args = ap.parse_args()

    print(f"[serve] compile cache: {enable_compile_cache()}")
    if args.opt:
        perf_flags.set_flags(**perf_flags.parse_opt(args.opt))
    engine, cfg = build_engine(args.model, args.slo, smoke=args.smoke,
                               heter=not args.no_heter,
                               npu_model=args.npu_model,
                               policy=args.policy, devices=args.devices,
                               npu_devices=args.npu_devices,
                               prewarm=args.prewarm,
                               hosts=args.hosts, replicas=args.replicas)
    queries = make_queries(args.queries, cfg.vocab_size, args.length)
    try:
        results, failures, _, wall = serve_burst(engine, queries,
                                                 args.length)
        report(engine, args.queries, wall, len(results) - len(failures),
               len(failures), args.slo)
    finally:
        engine.shutdown()
    print(f"[serve] clean shutdown: {engine.stats.clean_shutdown}")
    if failures:
        sys.exit(f"[serve] {len(failures)} accepted queries failed; "
                 f"first: {failures[0]!r}")


if __name__ == "__main__":
    main()
