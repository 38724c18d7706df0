#!/usr/bin/env python3
"""Readings for a cell's ``correct`` limit, many runs in one process::

    python3 bench/proof.py --workload bge.ingest --seconds 5 \
        --seeds 11,12,13 --precisions bf16,int8 \
        --faults tokens_shuffled,answer_altered

Runs the cell once per (precision, seed), and once per (fault, seed) in
the configuration's precision with the fault of ``bench/faults.py``
planted, exactly as ``bench/run.py`` does otherwise, and prints one JSON
line per run with the numbers compared and whether the run came out
correct.  The configuration's own precision gives the lower reading of
each limit; a lower precision (the program's ``int8`` path) is the
control, and the control and the faults give the upper readings.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path.pop(0)
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.faults import FAULTS  # noqa: E402
from bench.run import log, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precisions", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = [("precision", p, {"precision": p})
            for p in args.precisions.split(",") if p]
    runs += [("fault", f, {"fault": FAULTS[f]})
             for f in args.faults.split(",") if f]
    for kind, what, kw in runs:
        for seed in seeds:
            out = run_cell(ROOT, args.workload, seed, args.seconds, False,
                           **kw)
            line = {kind: what, "seed": seed, "correct": out["correct"],
                    "check": out["check"], "metrics": out["metrics"]}
            print(json.dumps(line))
            sys.stdout.flush()
            log(f"{what} seed {seed}: {out['check']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
