#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip this process holds::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The run

1. builds the serving engine the way ``repro.launch.serve`` does
   (Algorithm 2's device probe, one backend per tier, each tier's Eq. 12
   calibration, the cascade policy) in the configuration's precision;
2. compiles the (batch, sequence) shapes the mix can reach on each tier
   it names, and sends ``warmup_s`` seconds of the same traffic;
3. drives ``WindVE.submit`` from the mix for ``--seconds`` seconds,
   timing every query from when it was due to its future's completion;
4. with ``--trace 1``, traces the window with ``jax.profiler``;
5. compares a seeded sample of the embeddings served in the window with
   ``bench/reference.py``, which runs the configuration's architecture
   module (``bench/arch/<arch>.py``), and prints one JSON line.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics; each metric is read by
``bench/metrics/<name before the first dot>.py``.  Without a TPU, or with
fewer chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from collections import defaultdict  # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# run as a script, this directory heads sys.path; import the benchmark as a
# package instead, so its modules never shadow the standard library's
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path.pop(0)
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import arch, loadgen, reference, stats  # noqa: E402
from bench import trace as btrace  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
# a --trace 1 run measures its per-layer metrics over at most this many
# seconds: a TPU trace holds some 10^5 op events a second, and reading them
# back has to fit in the run's time limit
TRACE_SECONDS = 10.0
# seconds past the window's close that a run waits for answers still due
DRAIN_S = 60.0
# answers compared with the reference, per tier that served in the window
SAMPLE = {"NPU": 96, "CPU": 32}
# bound on the queries a closed loop may send per second of its horizon
CLOSED_MAX_QPS = 50_000


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(root: Path, name: str, bench: dict | None = None):
    """(bench, cell, config, mix) of the cell called ``name``, each found
    by its name: the cell in ``BENCHMARK.json``, its configuration's file
    as that lists it, the mix in ``bench/traffic/<traffic>.json``."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root / entry["file"])
    mix = loadgen.load_mix(root / "bench" / "traffic"
                           / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports:
    those that list it, and those that list no cells."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def reader(root: Path, metric: str):
    """``read(run)`` of ``bench/metrics/<name before the first dot>.py``."""
    base = metric.split(".")[0]
    path = root / "bench" / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chip(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(
            f"[bench] needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s): no result\n")
        raise NoChip(2)
    return devs


class GcClock:
    """Every collection the garbage collector makes: (start, generation,
    seconds), on the monotonic clock."""

    def __init__(self):
        self.events: list = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.events.append((self._t, info["generation"],
                                time.monotonic() - self._t))
            self._t = None

    def close(self):
        gc.callbacks.remove(self._on)

    def brief(self, w0: float, w1: float) -> str:
        """Collections started in [w0, w1), per generation: count, total and
        longest pause."""
        out = []
        for g in range(3):
            d = [s for t, gen, s in self.events if gen == g and w0 <= t < w1]
            if d:
                out.append(f"gen{g} {len(d)}x total {1e3 * sum(d):.1f}ms "
                           f"longest {1e3 * max(d):.1f}ms")
        return "; ".join(out) or "none"


def log(msg: str) -> None:
    sys.stderr.write(f"[bench] {msg}\n")
    sys.stderr.flush()


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds JAX reports spending per kind of compile-cache event, and
    the persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self.secs = defaultdict(float)
        self.count = defaultdict(int)
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on_event)

    def _on(self, event, duration, *args, **kwargs):
        self.secs[event] += duration

    def _on_event(self, event, *args, **kwargs):
        self.count[event] += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)
        self._mon.unregister_event_listener(self._on_event)

    def brief(self) -> str:
        keys = {"compile": "backend_compile", "cache_load":
                "cache_retrieval", "trace": "trace"}
        out = []
        for label, frag in keys.items():
            s = sum(v for k, v in self.secs.items() if frag in k)
            out.append(f"{label} {s:.1f}s")
        hits = sum(v for k, v in self.count.items() if "cache_hits" in k)
        miss = sum(v for k, v in self.count.items() if "cache_misses" in k)
        return ", ".join(out) + f", persistent cache {hits} hits {miss} misses"


def check_program_config(pcfg, cfg: dict) -> None:
    """The program's configuration must be the one that the configuration's
    architecture module expects of the file, attribute for attribute."""
    want = arch.of(cfg).program_fields(cfg)
    got = {k: getattr(pcfg, k) for k in want}
    if got != want:
        raise RuntimeError(f"the program serves {got}, the configuration "
                           f"file states {want}")


def program_init(mod):
    """(module, attribute name) of the program's initialisation that the
    architecture module's ``seeded_init`` wraps (``PROGRAM_INIT``)."""
    where, name = mod.PROGRAM_INIT.rsplit(".", 1)
    return importlib.import_module(where), name


def build(cfg: dict, seed: int):
    """The engine, as ``repro.launch.serve.build_engine`` builds it with the
    program's initialisation wrapped by the architecture's ``seeded_init``,
    and the seconds its parameter initialisation took."""
    import jax

    from repro.launch import serve

    mod = arch.of(cfg)
    owner, name = program_init(mod)
    init, spent = getattr(owner, name), [0.0]
    draw = mod.seeded_init(init, cfg["embedding_init_std"])

    def timed_init(*a, **k):
        t = time.monotonic()
        out = jax.block_until_ready(draw(*a, **k))
        spent[0] += time.monotonic() - t
        return out

    setattr(owner, name, timed_init)
    try:
        with contextlib.redirect_stdout(sys.stderr):
            engine, pcfg = serve.build_engine(
                cfg["model"], slo=cfg["slo_s"], seed=seed, prewarm=False)
    finally:
        setattr(owner, name, init)
    check_program_config(pcfg, cfg)
    return engine, spent[0]


def prewarm(engine, tiers, lengths) -> int:
    """Compile every (B, S) shape the named tiers can execute for these
    query lengths: each power-of-two batch bucket up to the tier's batch
    bound, times each sequence bucket the lengths round up to."""
    from repro.core.bucketing import bucket_length, next_pow2

    n = 0
    for name in tiers:
        be = engine.backends.get(name)
        if be is None:
            continue
        top = next_pow2(engine.qm.max_batch(name))
        bs, b = [], be.min_batch_bucket
        while b <= top:
            bs.append(b)
            b *= 2
        ss = sorted({bucket_length(int(x), be.min_seq_bucket, be.max_tokens)
                     for x in lengths})
        n += be.prewarm([(b, s) for b in bs for s in ss])
    return n


def counters(engine) -> dict:
    s = engine.stats
    out = {"per_device": dict(s.per_device),
           "batches": {t: len(v) for t, v in s.tier_batch_latencies.items()}}
    for name, be in engine.backends.items():
        out[name] = {k: getattr(be, k, 0) for k in
                     ("traces", "real_tokens", "padded_tokens", "truncated")}
    return out


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Driver:
    """Sends the mix's requests to the engine and records, per request, when
    it was due, submitted and done, and how it ended."""

    def __init__(self, engine, stream, req: stats.Requests, dim: int,
                 tracing: bool):
        self.engine, self.stream, self.req = engine, stream, req
        self.dim = dim
        self.results: dict = {}
        self.errors: list = []
        self.payload_index: dict = {}
        self.batches: list = []
        self.free = threading.Semaphore(0)
        self.tracing = tracing
        self.snap0 = self.snap1 = None
        self._window = None
        engine.add_batch_hook(self._on_batch)

    def _on_batch(self, tier, batch, service):
        t = time.monotonic()
        self.batches.append(stats.Batch(
            tier=tier, start=t - service, service=service,
            lengths=[len(q.payload) for q in batch],
            queue_wait=[q.done_t - service - q.arrival_t for q in batch],
            payload_ids=[id(q.payload) for q in batch]))

    def _on_done(self, i, fut):
        t = time.monotonic()
        exc = fut.exception()
        if exc is None:
            emb = fut.result()
            ok = (getattr(emb, "shape", None) == (self.dim,)
                  and bool(np.isfinite(emb).all())
                  and abs(float(np.dot(emb, emb)) - 1.0) < 1e-3)
            if ok:
                self.results[i] = emb
            else:
                exc = ValueError(f"malformed embedding for request {i}")
        if exc is not None and len(self.errors) < 5:
            self.errors.append(repr(exc))
        self.req.done[i] = t
        self.req.status[i] = stats.OK if exc is None else stats.FAILED
        self.free.release()

    def send(self, i: int) -> bool:
        payload = self.stream[i]
        self.payload_index[id(payload)] = i
        self.req.lengths[i] = len(payload)
        self.req.submit[i] = time.monotonic()
        fut = self.engine.submit(payload=payload, length=len(payload))
        if fut is None:
            self.req.status[i] = stats.BUSY
            return False
        fut.add_done_callback(partial(self._on_done, i))
        return True

    def _edge(self, now: float, w0: float, w1: float) -> None:
        """Snapshot the counters as the window opens and closes."""
        if self.snap0 is None and now >= w0:
            self.snap0 = counters(self.engine)
            if self.tracing:
                import jax

                self._window = jax.profiler.TraceAnnotation(btrace.WINDOW)
                self._window.__enter__()
        if self.snap1 is None and now >= w1:
            self.snap1 = counters(self.engine)
            if self._window is not None:
                self._window.__exit__(None, None, None)

    def _span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_loop(self, due_abs: np.ndarray, w0: float, w1: float) -> None:
        """Submit each request when it falls due, whatever is in flight."""
        n, i = len(due_abs), 0
        while i < n or self.snap1 is None:
            now = time.monotonic()
            self._edge(now, w0, w1)
            nxt = due_abs[i] if i < n else w1
            if nxt > now:
                time.sleep(min(nxt - now, 0.002))
                continue
            with self._span("bench.submit"):
                while i < n and due_abs[i] <= now:
                    self.req.due[i] = due_abs[i]
                    self.send(i)
                    i += 1

    def closed_loop(self, width: int, w0: float, w1: float) -> int:
        """Keep ``width`` requests outstanding until the window closes; a
        BUSY answer is sent again after 1 ms.  Returns the count sent."""
        i = 0
        cap = len(self.req.lengths)

        def send_next():
            nonlocal i
            if i >= cap:
                raise RuntimeError(f"closed loop ran past {cap} requests")
            self.req.due[i] = time.monotonic()
            while not self.send(i):
                time.sleep(0.001)
                self.req.status[i] = stats.PENDING
            i += 1

        with self._span("bench.submit"):
            for _ in range(width):
                send_next()
        while self.snap1 is None:
            now = time.monotonic()
            self._edge(now, w0, w1)
            if now >= w1:
                break
            if self.free.acquire(timeout=0.005):
                with self._span("bench.submit"):
                    send_next()
        return i

    def drain(self, n: int, until: float) -> None:
        while time.monotonic() < until:
            if not np.any(self.req.status[:n] == stats.PENDING):
                return
            time.sleep(0.01)

    def instrument(self) -> None:
        """Host spans around the calls the engine's workers make into each
        backend, for the trace's idle-gap labels (traced runs only)."""
        import jax

        for name, be in self.engine.backends.items():
            tag = name.lower()
            if hasattr(be, "embed_batch_async"):
                inner = be.embed_batch_async

                def enq(queries, inner=inner, tag=tag):
                    with jax.profiler.TraceAnnotation(f"bench.{tag}.stage"):
                        fetch = inner(queries)

                    def fetched(fetch=fetch):
                        with jax.profiler.TraceAnnotation(
                                f"bench.{tag}.fetch"):
                            return fetch()

                    return fetched

                be.embed_batch_async = enq


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def sample(run_req, driver: Driver, sel: np.ndarray, seed: int) -> dict:
    """Per tier, a seeded sample of the answers served in the window, the
    longest query of each tier among them."""
    tier_of = {}
    for b in driver.batches:
        for pid in b.payload_ids:
            i = driver.payload_index.get(pid)
            if i is not None:
                tier_of[i] = b.tier
    rng = np.random.default_rng(loadgen._seed(seed, 9))
    out = {}
    for tier, k in SAMPLE.items():
        idx = np.array([i for i in np.flatnonzero(sel)
                        if tier_of.get(int(i)) == tier and i in driver.results],
                       np.int64)
        if not idx.size:
            continue
        longest = idx[np.argmax(run_req.lengths[idx])]
        pick = rng.choice(idx, min(k, idx.size), replace=False)
        if longest not in pick:
            pick[0] = longest
        out[tier] = sorted(int(i) for i in pick)
    return out


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             *, bench: dict | None = None, config: dict | None = None,
             require_tpu: bool = True, precision: str | None = None,
             fault=None) -> dict:
    """One run of the cell ``name``; returns the result line's object.

    ``config``, ``require_tpu``, ``precision`` and ``fault`` exist for the
    tests and the control runs: a configuration other than the cell's file,
    a run on whatever JAX finds, another serving precision, and a callable
    that breaks the built engine before any traffic."""
    bench, cell, cfg_file, mix = cell_spec(root, name, bench)
    cfg = dict(config if config is not None else cfg_file)
    arch.load(root, cfg["arch"])
    if precision is not None:
        cfg["precision"] = precision
    if require_tpu:
        devs = require_chip(int(cell["chips"]))
    import jax

    from repro import perf_flags
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices() if not require_tpu else devs
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock()
    gcs = GcClock()
    flags = perf_flags.FLAGS
    perf_flags.set_flags(embed_dtype=cfg["precision"])
    try:
        return _run(root, bench, cell, cfg, mix, seed, seconds, trace, devs,
                    clock, cache_dir, fault, gcs)
    finally:
        perf_flags.FLAGS = flags
        clock.close()
        gcs.close()


def _run(root, bench, cell, cfg, mix, seed, seconds, trace, devs, clock,
         cache_dir, fault, gcs) -> dict:
    import jax

    t0 = time.monotonic()
    engine, init_s = build(cfg, seed)
    t_built = time.monotonic()
    if fault is not None:
        fault(engine)
    stream = loadgen.Stream(mix, seed, cfg["vocab_size"])
    n_warm = prewarm(engine, mix.get("prewarm", []), stream.lengths_used())
    t_warm = time.monotonic()
    log(f"set-up: imports {t0 - T_START:.1f}s, engine {t_built - t0:.1f}s "
        f"(param init {init_s:.1f}s, calibration and the rest "
        f"{t_built - t0 - init_s:.1f}s), prewarm of {n_warm} shapes "
        f"{t_warm - t_built:.1f}s; JAX {clock.brief()}; cache {cache_dir}")
    log("depths: " + " ".join(f"{t}={engine.qm.depth(t)}"
                              for t in engine.backends)
        + f"; max_concurrency={engine.max_concurrency}")

    warm = float(mix["warmup_s"])
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    horizon = warm + seconds
    if mix["loop"] == "open":
        offsets = loadgen.arrivals(mix, seed, horizon)
        req = stats.Requests(len(offsets), np.zeros(len(offsets), np.int64))
        for c in range(len(offsets) // loadgen.CHUNK + 1):
            stream.chunk(c)          # draw the inputs before the clock runs
    else:
        cap = engine.max_concurrency + int(CLOSED_MAX_QPS * horizon)
        req = stats.Requests(cap, np.zeros(cap, np.int64))
    driver = Driver(engine, stream, req, cfg["hidden_size"], trace)
    if trace:
        driver.instrument()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    start = time.monotonic() + 0.05
    w0, w1 = start + warm, start + horizon
    if mix["loop"] == "open":
        driver.open_loop(start + offsets, w0, w1)
        n = len(offsets)
    else:
        n = driver.closed_loop(engine.max_concurrency, w0, w1)
    driver.drain(n, w1 + DRAIN_S)
    setup_s = w0 - T_START
    mem = devs[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    summ = None
    if trace:
        jax.profiler.stop_trace()
        pb = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
        t_read = time.monotonic()
        summ = btrace.summarize(btrace.XplaneSource(sorted(pb)[-1]))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"trace read in {time.monotonic() - t_read:.1f}s: busy "
            f"{summ.busy_s:.4f}s, inside programs {summ.program_s:.4f}s, "
            f"of {summ.window_s:.4f}s")
        for what, table in (("programs", summ.module_time),
                            ("ops", summ.op_time)):
            top = sorted(table.items(), key=lambda kv: -kv[1])[:12]
            log(f"trace {what}: " + "; ".join(f"{k} {v:.4f}s" for k, v in top))

    req.status[:n][req.status[:n] == stats.PENDING] = stats.FAILED
    if mix["loop"] == "open":
        summary = stats.open_loop(req, w0, w1, cfg["slo_s"])
        sel = stats.due_in(req, w0, w1)
    else:
        summary = stats.closed_loop(req, w0, w1)
        sel = stats.done_in(req, w0, w1)
    c0, c1 = driver.snap0, driver.snap1
    compiled = {t: c1[t]["traces"] - c0[t]["traces"] for t in engine.backends
                if c1[t]["traces"] != c0[t]["traces"]}
    if compiled:
        log(f"compiles inside the window: {compiled}")
    log("window: " + " ".join(f"{k}={v}" for k, v in summary.items()))
    log(f"garbage collections in the window: {gcs.brief(w0, w1)}")
    log(f"per tier in the window: "
        + " ".join(f"{t}={c1['per_device'].get(t, 0) - c0['per_device'].get(t, 0)}"
                   for t in engine.backends))
    if driver.errors:
        log(f"first failures: {driver.errors}")

    run = stats.Run(cell=cell, config=cfg, mix=mix,
                    peaks=load_json(root / "bench" / "peaks.json"),
                    device_kind=devs[0].device_kind,
                    w0=w0, w1=w1, setup_s=setup_s, summary=summary,
                    requests=req, batches=driver.batches,
                    counters={"start": c0, "end": c1}, trace=summ)
    picks = sample(req, driver, sel, seed)
    served = {t: np.stack([driver.results[i] for i in ix])
              for t, ix in picks.items()}
    queries = {t: [stream[i] for i in ix] for t, ix in picks.items()}

    # the program's state goes before the reference runs
    engine.shutdown()
    del engine, driver
    gc.collect()

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in metrics_for(bench, cell["name"], kind):
        v = reader(root, m["name"])(run)
        if v is None:
            if trace:
                continue
            raise RuntimeError(f"{m['name']}: nothing to read")
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    numbers = compare(cfg, seed, served, queries)
    failed = int(summary["failed"])
    limits = cfg["limits"]
    correct = failed == 0 and all(numbers[k] <= v for k, v in limits.items())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": int(summary["attempted"]),
           "failed": failed, "metrics": metrics, "device": device}
    if summ is not None:
        device["busy_s"] = summ.busy_s
        device["window_s"] = summ.window_s
        out["breakdown"] = {"device_ops": summ.top_ops(10),
                            "idle_gaps": summ.idle_gaps[:10]}
        log("idle by host span: " + " ".join(
            f"{k}={v:.4f}s" for k, v in sorted(summ.idle_by_host.items(),
                                               key=lambda kv: -kv[1])))
    out["check"] = {k: {"value": numbers[k], "limit": v}
                    for k, v in limits.items()}
    out["check"]["failed"] = {"value": failed, "limit": 0}
    log(f"device: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']}")
    log("compared " + " ".join(f"{t}:{len(v)}" for t, v in served.items())
        + " answers with the reference; "
        + " ".join(f"{k}={v:.6g}" for k, v in numbers.items()))
    for k, v in limits.items():
        log(f"check: {k}={numbers[k]:.6g} limit={v:g}")
    log(f"check: failed={failed} limit=0")
    return out


def compare(cfg: dict, seed: int, served: dict, queries: dict) -> dict:
    """The numbers that decide ``correct``, from the sampled answers
    (``served``, per tier) and the reference's vectors for their queries:

    - ``max_l2_gap``: the widest L2 distance of a served vector from the
      reference's, over every sampled answer;
    - ``bias``: the L2 norm of the mean of (served - reference) over the
      sample.  Rounding errors of one answer point every way and cancel in
      the mean; a lower precision's error is a fixed change of the model
      and does not.

    With no answer to compare, each is 2.0, the widest two unit vectors
    can be apart."""
    if not served:
        return {"max_l2_gap": 2.0, "bias": 2.0}
    weights = reference.init_weights(cfg, seed)
    tiers = list(served)
    ref = reference.embed(cfg, seed, [q for t in tiers for q in queries[t]],
                          weights=weights)
    del weights
    got = np.concatenate([served[t] for t in tiers]).astype(np.float64)
    diff = got - ref
    gaps = np.linalg.norm(diff, axis=-1)
    k = 0
    for t in tiers:
        g = gaps[k:k + len(served[t])]
        log(f"{t}: {len(g)} answers, L2 gap max {g.max():.6g} median "
            f"{np.median(g):.6g}, bias {np.linalg.norm(diff[k:k + len(g)].mean(0)):.6g}")
        k += len(g)
    return {"max_l2_gap": float(gaps.max()),
            "bias": float(np.linalg.norm(diff.mean(0)))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache lives inside the checkout (``.jax_cache``), whatever
    # the environment names, so that two checkouts never share one
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(out))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
