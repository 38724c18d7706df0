"""The engine's own host spans in a profiler trace, on the device trace's
clock: what the accelerator worker was doing in each idle gap, and the
per-layer numbers those spans give.

The serving engine opens one ``jax.profiler.TraceAnnotation`` span per
host phase (``repro.core.telemetry.HostSpans``), named
``windve.<tier>.<phase>`` with ``<tier>`` the tier's name in lower case.
A worker's ``wait``, ``pop``, ``stage``, ``fetch``, ``complete`` and
``hooks`` spans tile its thread's time; ``tokenize``, ``device_put`` and
``dispatch`` nest in ``stage`` once per chunk, ``ready`` (the host waits for
the results: the executions and the device-to-host transfers) and ``copy``
(the per-row split on the host) in ``fetch``.  ``windve.submit`` is one query's ``WindVE.submit``.

``bench/trace.py`` reads the ``bench.`` spans alone, and ``bench/run.py``
deletes a traced run's trace once it is summarised.  Until the benchmark
reads these spans itself, this module makes one traced run of a cell and
prints what they show beside the run's result line::

    PYTHONPATH=src python3 -m bench.spans --workload <cell> --seed <n> \\
        --seconds <s> [--record <path>]

``--record`` writes a short stretch of the trace around its longest idle
gap in ``bench.trace.JsonSource``'s format, program spans kept; ``--cost``
prints what a span costs on this host with no profiler session.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import trace as btrace
from bench.stats import percentile

ROOT = Path(__file__).resolve().parents[1]
PREFIX = "windve."
# a worker's phases, which tile its thread's time
WORKER = ("wait", "pop", "stage", "fetch", "complete", "hooks")
# phases that nest in ``stage`` or ``fetch``
CHILDREN = frozenset({"tokenize", "device_put", "dispatch", "ready", "copy"})
# the host's part of a batch outside the device's execution (none nests in
# another): ``npu_host_ms_per_batch``
HOST_PER_BATCH = ("pop", "stage", "copy", "complete", "hooks")
# the size a recorded stretch aims under
RECORD_BYTES = 480_000


class XplaneSource(btrace.XplaneSource):
    """A ``.xplane.pb`` file's events, with its program spans."""

    def program_events(self) -> Iterator[btrace.HostEvent]:
        for plane in self._data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        yield ev.name, ev.start_ns, ev.duration_ns


class JsonSource(btrace.JsonSource):
    """A recorded trace whose ``host`` list holds program spans beside the
    benchmark's: ``host_events`` yields the benchmark's alone, as an
    ``XplaneSource`` does, and ``program_events`` the program's."""

    def host_events(self) -> Iterator[btrace.HostEvent]:
        for ev in super().host_events():
            if ev[0].startswith(btrace.HOST_PREFIX):
                yield ev

    def program_events(self) -> Iterator[btrace.HostEvent]:
        for name, start, dur in self._data["host"]:
            if name.startswith(PREFIX):
                yield name, start, dur


def phase(name: str) -> str:
    """``windve.npu.stage`` -> ``stage``."""
    return name.rsplit(".", 1)[-1]


@dataclass
class ProgramSummary:
    """A trace's program spans clipped to the window; times in seconds."""

    window_s: float
    idle_s: float                 # device idle, mean over the device planes
    host_bound_idle_s: float      # of it, outside programs and ``wait``
    covered_s: float              # the worker's spans' union in the window
    stage_s: List[float] = field(default_factory=list)
    host_s: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[List] = field(default_factory=list)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    def metrics(self) -> Dict[str, float]:
        """The per-layer numbers, by metric name before its cell suffix;
        one that has nothing to read is left out."""
        out = {"host_bound_idle_pct":
               100.0 * self.host_bound_idle_s / self.window_s}
        if self.stage_s:
            out["npu_stage_p50_ms"] = 1e3 * percentile(self.stage_s, 50)
            out["npu_host_ms_per_batch"] = 1e3 * sum(
                self.host_s.get(p, 0.0)
                for p in HOST_PER_BATCH) / len(self.stage_s)
        return out

    @property
    def coverage_pct(self) -> float:
        """Share of the window the accelerator worker's spans cover."""
        return 100.0 * self.covered_s / self.window_s


def _window(source) -> Tuple[float, float]:
    wins = [(s, s + d) for n, s, d in source.host_events()
            if n == btrace.WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {btrace.WINDOW!r} span")
    return wins[0]


def device_intervals(source, w0: float, w1: float):
    """Per device plane, the op intervals (busy) and the program
    executions, clipped to [w0, w1), as ``bench.trace.summarize`` counts
    them: control-flow ops are left out of busy time."""
    busy, progs = defaultdict(list), defaultdict(list)
    labels: Dict[str, str] = {}
    for plane, line, name, s, d in source.device_events():
        a, b = max(s, w0), min(s + d, w1)
        if b <= a:
            continue
        if line == btrace.OPS_LINE:
            label = labels.get(name)
            if label is None:
                label = labels[name] = btrace.op_label(name)
            if label.split(" ", 1)[0] not in btrace.CONTAINERS:
                busy[plane].append((a, b))
        elif line == btrace.MODULES_LINE:
            progs[plane].append((a, b))
    if not busy:
        raise ValueError("the trace holds no device op inside the window")
    return busy, progs


def program_summary(source, accel: str = "npu",
                    offload: Sequence[str] = ("cpu",),
                    top_gaps: int = 10) -> ProgramSummary:
    """Reduce a trace's program spans over its ``bench.window``:

    - the device's idle time outside program executions and outside every
      ``windve.<accel>.wait`` span (the part the host, not the arrival
      rate, is responsible for), per device plane, averaged;
    - the durations of the accelerator tier's spans that start in the
      window, per phase, and of its ``stage`` spans one by one;
    - the union of its worker's spans in the window;
    - the idle gaps of the first device plane, each named by
      ``GapLabeller``, the ``top_gaps`` longest and the total per name.
    """
    w0, w1 = _window(source)
    busy, progs = device_intervals(source, w0, w1)
    mine, other = f"{PREFIX}{accel}.", tuple(f"{PREFIX}{t}." for t in offload)
    spans = [(n, s, s + d) for n, s, d in source.program_events()
             if n.startswith(mine) or n.startswith(other)]
    acc = [(n, s, e) for n, s, e in spans if n.startswith(mine)]

    waits = [(max(s, w0), min(e, w1)) for n, s, e in acc
             if phase(n) == "wait" and e > w0 and s < w1]
    planes = sorted(busy)
    window = w1 - w0
    idle = [window - btrace.union_length(busy[p]) for p in planes]
    host_bound = [window - btrace.union_length(busy[p] + progs[p] + waits)
                  for p in planes]
    covered = btrace.union_length(
        (max(s, w0), min(e, w1)) for n, s, e in acc
        if phase(n) in WORKER and e > w0 and s < w1)
    host_s: Dict[str, float] = defaultdict(float)
    stage_s = []
    for n, s, e in acc:
        if w0 <= s < w1:
            host_s[phase(n)] += (e - s) * 1e-9
            if phase(n) == "stage":
                stage_s.append((e - s) * 1e-9)

    label = GapLabeller(spans, progs[planes[0]], mine)
    gaps, by_span = [], defaultdict(float)
    for gs, ge in btrace.gaps(busy[planes[0]], w0, w1):
        name = label(gs, ge)
        gaps.append([name, (ge - gs) * 1e-9])
        by_span[name] += (ge - gs) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return ProgramSummary(
        window_s=window * 1e-9,
        idle_s=sum(idle) / len(idle) * 1e-9,
        host_bound_idle_s=sum(host_bound) / len(host_bound) * 1e-9,
        covered_s=covered * 1e-9, stage_s=stage_s, host_s=dict(host_s),
        idle_gaps=gaps[:top_gaps], idle_by_span=dict(by_span))


class GapLabeller:
    """Names an idle gap: ``in-program`` for a gap inside one program
    execution; otherwise the accelerator worker's deepest span that covers
    at least half of the gap (the one that covers most where none does),
    with the offload tier's deepest span that covers half of it appended
    (``windve.npu.tokenize+windve.cpu.ready``), or ``idle`` where no span
    touches the gap.  An offload worker's ``wait`` contends for nothing
    and is never appended."""

    def __init__(self, spans, progs, accel_prefix: str):
        self.progs = sorted(progs)
        self.prog_starts = [s for s, _ in self.progs]
        self.spans = sorted(spans, key=lambda x: x[1])
        self.span_starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in spans), default=0)
        self.mine = accel_prefix

    @staticmethod
    def _deepest(over: Dict[str, float], need: float) -> Optional[str]:
        full = [n for n, o in over.items() if o >= need]
        if not full:
            return None
        return max(full, key=lambda n: (phase(n) in CHILDREN, over[n], n))

    def in_program(self, gs: float, ge: float) -> bool:
        i = bisect.bisect_right(self.prog_starts, gs) - 1
        return i >= 0 and self.progs[i][1] >= ge

    def __call__(self, gs: float, ge: float) -> str:
        if self.in_program(gs, ge):
            return "in-program"
        mine, theirs = defaultdict(float), defaultdict(float)
        lo = bisect.bisect_left(self.span_starts, gs - self.longest)
        hi = bisect.bisect_left(self.span_starts, ge)
        for n, s, e in self.spans[lo:hi]:
            o = min(e, ge) - max(s, gs)
            if o <= 0:
                continue
            if n.startswith(self.mine):
                mine[n] += o
            elif phase(n) != "wait":
                theirs[n] += o
        half = (ge - gs) / 2
        a = self._deepest(mine, half) or (
            max(mine, key=mine.get) if mine else None)
        b = self._deepest(theirs, half)
        if a is None and b is None and theirs:
            b = max(theirs, key=theirs.get)
        return "+".join(n for n in (a, b) if n) or "idle"


def record(source, path: str, around: int = 2) -> dict:
    """Write the stretch of ``source`` from the ``around``-th program
    before its longest idle gap outside a program to the ``around``-th
    after it, as a ``JsonSource`` file: that stretch as ``bench.window``,
    the ``bench.`` and program spans that overlap it, and its device
    events, each op named by its ``bench.trace.op_label`` (which is its own
    label) to keep the file small.  Times are nanoseconds from the
    stretch's start.  Returns the file's contents."""
    w0, w1 = _window(source)
    busy, progs = device_intervals(source, w0, w1)
    first = sorted(busy)[0]
    label = GapLabeller([], progs[first], PREFIX)
    mod = label.progs
    gap = max((g for g in btrace.gaps(busy[first], w0, w1)
               if not label.in_program(*g)),
              key=lambda g: g[1] - g[0])
    before = [s for s, e in mod if e <= gap[0]]
    after = [e for s, e in mod if s >= gap[1]]
    t0 = before[-around] if len(before) >= around else w0
    t1 = after[around - 1] if len(after) >= around else w1

    def keep(s, d):
        return s < t1 and s + d > t0

    host = [[btrace.WINDOW, 0, int(t1 - t0)]]
    host += [[n, int(s - t0), int(d)] for n, s, d in source.host_events()
             if n != btrace.WINDOW and keep(s, d)]
    host += [[n, int(s - t0), int(d)] for n, s, d in source.program_events()
             if keep(s, d)]
    device = [[p, line, btrace.op_label(n) if line == btrace.OPS_LINE else n,
               int(s - t0), int(d)]
              for p, line, n, s, d in source.device_events() if keep(s, d)]
    data = {"host": host, "device": device}
    with open(path, "w") as f:
        json.dump(data, f, separators=(",", ":"))
    return data


def span_cost(n: int = 200_000) -> Dict[str, float]:
    """Microseconds per span on this host with no profiler session: a bare
    ``TraceAnnotation``, one with a keyword, and the engine's
    ``HostSpans.span`` without and with a batch number."""
    from jax.profiler import TraceAnnotation

    from repro.core.telemetry import HostSpans

    spans = HostSpans("npu")
    cases = {"trace_annotation": lambda: TraceAnnotation("windve.npu.stage"),
             "trace_annotation_kw": lambda: TraceAnnotation(
                 "windve.npu.stage", batch=1),
             "host_span": lambda: spans.span("stage"),
             "host_span_batch": lambda: spans.span("stage", 1)}
    out = {}
    for name, make in cases.items():
        t = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        out[name] = (time.perf_counter() - t) / n * 1e6
    return out


def cell_suffix(bench: dict, cell: str) -> str:
    """The suffix the cell's per-layer metrics carry (``query`` for
    ``device_idle_pct.query``), '' where they carry none."""
    for m in bench["per_layer"]:
        base, _, suffix = m["name"].partition(".")
        if base == "device_idle_pct" and cell in m.get("workloads", [cell]):
            return suffix
    return ""


def traced_run(root: Path, name: str, seed: int, seconds: float,
               record_to: Optional[str] = None, **kw):
    """One ``--trace 1`` run of the cell through ``bench.run.run_cell``,
    which summarises its trace and then deletes it: the program spans are
    read from the same trace as it is summarised.  Returns the run's result
    and the ``ProgramSummary``."""
    from bench import run as brun

    held = {}
    source_cls, summarize = btrace.XplaneSource, btrace.summarize

    def with_spans(source, *a, **k):
        held["summary"] = program_summary(source)
        if record_to:
            for around in (2, 1):     # keep the file under RECORD_BYTES
                record(source, record_to, around)
                if os.path.getsize(record_to) <= RECORD_BYTES:
                    break
        return summarize(source, *a, **k)

    btrace.XplaneSource, btrace.summarize = XplaneSource, with_spans
    try:
        out = brun.run_cell(root, name, seed, seconds, True, **kw)
    finally:
        btrace.XplaneSource, btrace.summarize = source_cls, summarize
    return out, held.get("summary")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--record", help="write a stretch of the trace here")
    ap.add_argument("--cost", action="store_true",
                    help="print a span's cost with no profiler session")
    args = ap.parse_args(argv)
    if args.cost:
        print(json.dumps(span_cost()))
        return 0
    if args.workload is None or args.seed is None or args.seconds is None:
        ap.error("--workload, --seed and --seconds are required")
    # as bench/run.py: the compile cache lives inside the checkout
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    out, summ = traced_run(ROOT, args.workload, args.seed, args.seconds,
                           record_to=args.record)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    sfx = cell_suffix(bench, args.workload)
    batches = max(len(summ.stage_s), 1)
    out["program"] = {
        "metrics": {f"{k}.{sfx}" if sfx else k: v
                    for k, v in summ.metrics().items()},
        "worker_coverage_pct": summ.coverage_pct,
        "host_ms_per_batch": {p: 1e3 * v / batches
                              for p, v in sorted(summ.host_s.items())},
        "idle_gaps_program": summ.idle_gaps,
    }
    sys.stderr.write("[bench] idle by program span: " + " ".join(
        f"{k}={v:.4f}s" for k, v in sorted(summ.idle_by_span.items(),
                                            key=lambda kv: -kv[1])) + "\n")
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
