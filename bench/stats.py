"""Arithmetic from per-request records to the benchmark's numbers.

Every request carries the time it was due (open loop: its scheduled
arrival; closed loop: the moment it was handed to the engine), the time its
future completed, and a status.  Latency is always taken from the due time,
so a generator that fell behind is charged to the system, never hidden.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

PENDING, OK, BUSY, FAILED = 0, 1, 2, 3


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile; None for no samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median (Python's
    ``statistics.quantiles``, the rule the bounds are set by)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


@dataclass
class Requests:
    """Per-request records, filled by the load generator and done-callbacks."""

    n: int
    lengths: np.ndarray
    due: np.ndarray = None
    submit: np.ndarray = None
    done: np.ndarray = None
    status: np.ndarray = None

    def __post_init__(self):
        if self.due is None:
            self.due = np.full(self.n, np.nan)
        if self.submit is None:
            self.submit = np.full(self.n, np.nan)
        if self.done is None:
            self.done = np.full(self.n, np.nan)
        if self.status is None:
            self.status = np.zeros(self.n, np.int8)


def due_in(req: Requests, w0: float, w1: float) -> np.ndarray:
    return (req.due >= w0) & (req.due < w1)


def done_in(req: Requests, w0: float, w1: float) -> np.ndarray:
    return (req.status == OK) & (req.done >= w0) & (req.done < w1)


def open_loop(req: Requests, w0: float, w1: float, slo_s: float) -> Dict:
    """Numbers of an open-loop window: every query due in [w0, w1).

    A query rejected as BUSY, one whose future failed, and one that never
    completed all miss the SLO.  Latency percentiles are over the completed
    ones, in milliseconds, from the due time."""
    sel = due_in(req, w0, w1)
    ok = sel & (req.status == OK)
    lat = req.done[ok] - req.due[ok]
    met = int(np.count_nonzero(lat <= slo_s))
    late = req.submit[sel] - req.due[sel]
    late = late[np.isfinite(late)]
    return {
        "attempted": int(np.count_nonzero(sel)),
        "completed": int(np.count_nonzero(ok)),
        "busy": int(np.count_nonzero(sel & (req.status == BUSY))),
        "failed": int(np.count_nonzero(sel & (req.status == FAILED))),
        "unfinished": int(np.count_nonzero(sel & (req.status == PENDING))),
        "p50_ms": _ms(percentile(lat, 50)),
        "p95_ms": _ms(percentile(lat, 95)),
        "p99_ms": _ms(percentile(lat, 99)),
        "met_slo": met,
        "goodput_qps": met / (w1 - w0),
        "late_p50_ms": _ms(percentile(late, 50)),
        "late_p99_ms": _ms(percentile(late, 99)),
        "late_max_ms": _ms(float(late.max()) if late.size else None),
    }


def closed_loop(req: Requests, w0: float, w1: float) -> Dict:
    """Numbers of a closed-loop window: work completed in [w0, w1)."""
    ok = done_in(req, w0, w1)
    sub = (req.submit >= w0) & (req.submit < w1)
    return {
        "attempted": int(np.count_nonzero(sub)),
        "completed": int(np.count_nonzero(ok)),
        "failed": int(np.count_nonzero(sub & (req.status == FAILED))),
        "tokens": int(req.lengths[ok].sum()),
        "ingest_tokens_s": float(req.lengths[ok].sum()) / (w1 - w0),
    }


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else x * 1e3


@dataclass
class Batch:
    """One batch the engine completed, as its batch hook reported it."""

    tier: str
    start: float                 # done - service, on the monotonic clock
    service: float
    lengths: List[int]
    queue_wait: List[float]      # done - service - submit, per query
    payload_ids: List[int]


@dataclass
class Run:
    """Everything a metric reader may read from one run."""

    cell: dict
    config: dict
    mix: dict
    peaks: dict
    device_kind: str
    w0: float
    w1: float
    setup_s: float
    summary: Dict
    requests: Requests
    batches: List[Batch] = field(default_factory=list)
    counters: Dict[str, Dict[str, int]] = field(default_factory=dict)
    trace: object = None         # bench.trace.Summary, --trace 1 only

    def peak(self, key: str) -> float:
        """The chip's published peak ``key`` (``bench/peaks.json``); a
        device missing from the table is an error, never a default."""
        if self.device_kind not in self.peaks:
            raise KeyError(f"no peaks for device kind {self.device_kind!r}")
        return float(self.peaks[self.device_kind][key])

    @property
    def seconds(self) -> float:
        return self.w1 - self.w0

    def window_batches(self, tier: Optional[str] = None) -> List[Batch]:
        return [b for b in self.batches if self.w0 <= b.start < self.w1
                and (tier is None or b.tier == tier)]
