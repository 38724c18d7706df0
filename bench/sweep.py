#!/usr/bin/env python3
"""Find an open-loop cell's knee: one engine, a fixed rate per step::

    python3 bench/sweep.py --workload bge.query_steady --seed <n> \
        --rates 1500,2000,2500 --seconds 10

For each rate the cell's mix runs ``warmup_s`` seconds and then a window of
``--seconds``; one JSON line per rate gives the share of queries due in the
window that completed within the SLO of their due time, the latency
percentiles, and ``growth_ms``: the median latency of the window's last
quarter less that of its first, which stays near zero without a growing
backlog.  The knee is the highest rate with at least 90% within the SLO
and no growth.  The cell's rate is then written into its mix by hand.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import loadgen, stats  # noqa: E402
from bench.run import (Driver, build, cell_spec, log, prewarm,  # noqa: E402
                       require_chip)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    _, cell, cfg, mix = cell_spec(ROOT, args.workload)
    if mix["loop"] != "open":
        raise SystemExit("a knee is a rate: the cell's loop must be open")
    require_chip(int(cell["chips"]))
    from repro import perf_flags
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    perf_flags.set_flags(embed_dtype=cfg["precision"])
    engine, _ = build(cfg, args.seed)
    stream = loadgen.Stream(mix, args.seed, cfg["vocab_size"])
    prewarm(engine, mix.get("prewarm", []), stream.lengths_used())
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            step = dict(mix, rate_qps=rate)
            warm = float(step["warmup_s"])
            offsets = loadgen.arrivals(step, args.seed, warm + args.seconds)
            req = stats.Requests(len(offsets), np.zeros(len(offsets), int))
            for c in range(len(offsets) // loadgen.CHUNK + 1):
                stream.chunk(c)
            driver = Driver(engine, stream, req, cfg["hidden_size"], False)
            start = time.monotonic() + 0.05
            w0, w1 = start + warm, start + warm + args.seconds
            driver.open_loop(start + offsets, w0, w1)
            driver.drain(len(offsets), w1 + 60.0)
            engine.remove_batch_hook(driver._on_batch)
            s = stats.open_loop(req, w0, w1, cfg["slo_s"])
            sel = stats.due_in(req, w0, w1) & (req.status == stats.OK)
            due = req.due[sel]
            lat = req.done[sel] - due
            q = args.seconds / 4
            first = stats.percentile(lat[due < w0 + q], 50) or 0.0
            last = stats.percentile(lat[due >= w1 - q], 50) or 0.0
            s.update(rate_qps=rate,
                     within_slo_pct=100.0 * s["met_slo"] / s["attempted"],
                     growth_ms=(last - first) * 1e3)
            print(json.dumps(s))
            sys.stdout.flush()
            log(f"rate {rate:g}: {s['within_slo_pct']:.1f}% within the SLO, "
                f"p50 {s['p50_ms']:.1f} ms, p99 {s['p99_ms']:.1f} ms, "
                f"growth {s['growth_ms']:.1f} ms, busy {s['busy']}")
            time.sleep(2.0)
    finally:
        engine.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
