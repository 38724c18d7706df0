"""Reduction of a profiler trace to device busy time, op and kernel time,
and idle gaps labelled by what the host was doing.

A source yields two kinds of events, both on the profiler's clock in
nanoseconds:

- host spans ``(name, start, duration)``: the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``), one
  of which, ``bench.window``, marks the measured window;
- device events ``(plane, line, name, start, duration)`` from the device
  planes (``/device:TPU:<n>``).  The ``XLA Ops`` line holds one event per
  executed operation, the ``XLA Modules`` line one per executed program.

On a TPU an op event is named by its HLO instruction's full text
(``%fusion.82 = bf16[64,96,1024]{...} fusion(...), kind=kOutput, ...``).
``op_label`` keeps what is stable across compilations: the opcode, a
fusion's kind or a custom call's target, the output shape and, for a
custom call, its first operand's shape.  Control-flow ops (``while``,
``conditional``, ``call``) span the ops of their bodies and are left out of
busy time and of the op table.

``XplaneSource`` reads the ``.xplane.pb`` that ``jax.profiler`` writes;
``JsonSource`` reads the same events from a small JSON file (the recorded
trace the tests use).  ``summarize`` clips everything to the window.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = frozenset({"while", "conditional", "call"})

_HLO = re.compile(r"%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\((\w+\[[\d,]*\])?")
_LAYOUT = re.compile(r"\{[^}]*\}")
_KIND = re.compile(r"kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(name: str) -> str:
    """``opcode[:kind|:target] output_shape[ <- first_operand_shape]`` of an
    HLO op event; a name that is no HLO text is its own label."""
    m = _HLO.match(name)
    if not m:
        return name
    shape, opcode, arg = m.groups()
    shape = _LAYOUT.sub("", shape)
    extra = _KIND.search(name) if opcode == "fusion" else (
        _TARGET.search(name) if opcode == "custom-call" else None)
    label = opcode + (f":{extra.group(1)}" if extra else "") + f" {shape}"
    if opcode == "custom-call" and arg:
        label += f" <- {arg}"
    return label


HostEvent = Tuple[str, float, float]
DeviceEvent = Tuple[str, str, str, float, float]


class XplaneSource:
    """Events of a ``.xplane.pb`` file, read with ``jax.profiler``."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        self._data = ProfileData.from_file(path)

    def host_events(self) -> Iterator[HostEvent]:
        for plane in self._data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        yield ev.name, ev.start_ns, ev.duration_ns

    def device_events(self) -> Iterator[DeviceEvent]:
        for plane in self._data.planes:
            if not plane.name.startswith(DEVICE_PREFIX):
                continue
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    yield (plane.name, line.name, ev.name, ev.start_ns,
                           ev.duration_ns)


class JsonSource:
    """Events of a JSON file ``{"host": [[name, start, dur], ...],
    "device": [[plane, line, name, start, dur], ...]}``."""

    def __init__(self, path: str):
        with open(path) as f:
            self._data = json.load(f)

    def host_events(self) -> Iterator[HostEvent]:
        for name, start, dur in self._data["host"]:
            yield name, start, dur

    def device_events(self) -> Iterator[DeviceEvent]:
        for ev in self._data["device"]:
            yield tuple(ev)


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], w0: float, w1: float
         ) -> List[Tuple[float, float]]:
    """The parts of [w0, w1) that no interval covers."""
    out, cur = [], w0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, w1)))
        cur = max(cur, e)
        if cur >= w1:
            break
    if cur < w1:
        out.append((cur, w1))
    return [(s, e) for s, e in out if e > s]


@dataclass
class Summary:
    """A trace clipped to the window; times in seconds."""

    window_s: float
    busy_s: float                      # mean over the device planes
    program_s: float                   # time inside program executions
    devices: int
    op_time: Dict[str, float] = field(default_factory=dict)
    op_count: Dict[str, int] = field(default_factory=dict)
    module_time: Dict[str, float] = field(default_factory=dict)
    module_count: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_host: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, pattern: str) -> Tuple[float, int]:
        """(seconds, count) of ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        keys = [k for k in self.op_time if rx.search(k)]
        return (sum(self.op_time[k] for k in keys),
                sum(self.op_count[k] for k in keys))

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        """(seconds, count) of program executions whose name matches."""
        rx = re.compile(pattern)
        keys = [k for k in self.module_time if rx.search(k)]
        return (sum(self.module_time[k] for k in keys),
                sum(self.module_count[k] for k in keys))

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_time.items(),
                                          key=lambda kv: -kv[1])[:n]]


def summarize(source, top_gaps: int = 10) -> Summary:
    """Clip the source's device events to the ``bench.window`` span and
    reduce them: busy time (the union of op intervals, averaged over the
    device planes), time and count per op label and per program name, and
    the idle gaps of the first device plane, each named by
    ``_GapLabeller``."""
    host = [e for e in source.host_events()]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w0, w1 = wins[0]
    spans = [(n, s, s + d) for n, s, d in host if n != WINDOW]

    busy, progs = defaultdict(list), defaultdict(list)
    op_t, op_n = defaultdict(float), defaultdict(int)
    mod_t, mod_n = defaultdict(float), defaultdict(int)
    labels: Dict[str, str] = {}
    for plane, line, name, s, d in source.device_events():
        a, b = max(s, w0), min(s + d, w1)
        if b <= a and not (d == 0 and w0 <= s < w1):
            continue
        if line == OPS_LINE:
            label = labels.get(name)
            if label is None:
                label = labels[name] = op_label(name)
            if label.split(" ", 1)[0] in CONTAINERS:
                continue
            busy[plane].append((a, b))
            op_t[label] += (b - a) * 1e-9
            op_n[label] += 1
        elif line == MODULES_LINE:
            progs[plane].append((a, b))
            mod_t[name] += (b - a) * 1e-9
            mod_n[name] += 1
    if not busy:
        raise ValueError("the trace holds no device op inside the window")
    planes = sorted(busy)
    busy_ns = [union_length(busy[p]) for p in planes]
    prog_ns = [union_length(progs[p]) for p in planes]

    labelled = []
    by_host = defaultdict(float)
    label_gap = _GapLabeller(spans, progs[planes[0]])
    for gs, ge in gaps(busy[planes[0]], w0, w1):
        label = label_gap(gs, ge)
        labelled.append((label, (ge - gs) * 1e-9))
        by_host[label] += (ge - gs) * 1e-9
    labelled.sort(key=lambda x: -x[1])
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy_ns) / len(busy_ns) * 1e-9,
        program_s=sum(prog_ns) / len(prog_ns) * 1e-9,
        devices=len(planes),
        op_time=dict(op_t), op_count=dict(op_n),
        module_time=dict(mod_t), module_count=dict(mod_n),
        idle_gaps=[[n, t] for n, t in labelled[:top_gaps]],
        idle_by_host=dict(by_host))


class _GapLabeller:
    """Names an idle gap: ``in-program`` for a gap between two ops of one
    program execution; otherwise the host spans that cover at least half
    of the gap (the one that covers most where none does), or ``idle``."""

    def __init__(self, spans, progs):
        self.progs = sorted(progs)
        self.prog_starts = [s for s, _ in self.progs]
        self.spans = sorted(spans, key=lambda x: x[1])
        self.span_starts = [s for _, s, _ in self.spans]
        self.longest = max((e - s for _, s, e in spans), default=0)

    def __call__(self, gs, ge) -> str:
        i = bisect.bisect_right(self.prog_starts, gs) - 1
        if i >= 0 and self.progs[i][1] >= ge:
            return "in-program"
        over = defaultdict(float)
        lo = bisect.bisect_left(self.span_starts, gs - self.longest)
        hi = bisect.bisect_left(self.span_starts, ge)
        for n, s, e in self.spans[lo:hi]:
            o = min(e, ge) - max(s, gs)
            if o > 0:
                over[n] += o
        if not over:
            return "idle"
        most = sorted(n for n, o in over.items() if o >= (ge - gs) / 2)
        return "+".join(most) if most else max(over, key=over.get)
