"""Metric arithmetic on synthetic completions."""
import numpy as np
import pytest

from bench import stats


def requests(due, done, status, lengths=None):
    n = len(due)
    r = stats.Requests(n, np.asarray(lengths if lengths is not None
                                     else [10] * n, np.int64))
    r.due[:] = due
    r.submit[:] = due
    r.done[:] = done
    r.status[:] = status
    return r


OK, BUSY, FAILED, PENDING = stats.OK, stats.BUSY, stats.FAILED, \
    stats.PENDING


def test_latency_is_taken_from_the_due_time():
    r = requests([1.0, 2.0], [1.5, 2.25], [OK, OK])
    r.submit[:] = [1.4, 2.2]              # a generator that ran late
    s = stats.open_loop(r, 0.0, 10.0, slo_s=1.0)
    assert s["p50_ms"] == pytest.approx(375.0)
    assert s["late_max_ms"] == pytest.approx(400.0)


def test_goodput_counts_busy_failed_and_unfinished_as_misses():
    due = [0.1, 0.2, 0.3, 0.4, 0.5, 0.6]
    done = [0.2, 2.0, np.nan, 0.5, np.nan, np.nan]
    r = requests(due, done, [OK, OK, BUSY, FAILED, PENDING, OK])
    r.done[5] = 0.7
    s = stats.open_loop(r, 0.0, 2.0, slo_s=1.0)
    # met the SLO: the first (0.1 s) and the last (0.1 s); the second took
    # 1.8 s; busy, failed and unfinished ones miss
    assert s["met_slo"] == 2
    assert s["goodput_qps"] == pytest.approx(1.0)
    assert (s["attempted"], s["completed"], s["busy"], s["failed"],
            s["unfinished"]) == (6, 3, 1, 1, 1)


def test_window_holds_only_queries_due_inside_it():
    r = requests([0.5, 1.5, 2.5], [0.6, 1.6, 2.6], [OK, OK, OK])
    s = stats.open_loop(r, 1.0, 2.0, slo_s=1.0)
    assert s["attempted"] == 1 and s["goodput_qps"] == pytest.approx(1.0)


def test_percentiles_interpolate_linearly():
    lat = np.arange(1, 101, dtype=float)           # 1..100 ms
    r = requests(np.zeros(100), lat / 1e3, [OK] * 100)
    s = stats.open_loop(r, 0.0, 1.0, slo_s=1.0)
    assert s["p50_ms"] == pytest.approx(50.5)
    assert s["p99_ms"] == pytest.approx(99.01)
    assert stats.percentile([], 50) is None


def test_closed_loop_counts_real_tokens_completed_in_the_window():
    r = requests([0.0, 0.5, 0.9, 1.5], [0.8, 1.2, 2.5, 1.9],
                 [OK, OK, OK, FAILED], lengths=[10, 20, 30, 40])
    s = stats.closed_loop(r, 1.0, 2.0)
    assert s["tokens"] == 20                     # only the second lands in
    assert s["ingest_tokens_s"] == pytest.approx(20.0)
    assert s["failed"] == 1                      # submitted in the window


def test_spread_is_the_interquartile_share_of_the_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)
