"""The program-span readers (``bench/spans.py``): host-bound idle time, the
accelerator tier's staging and host time per batch, and idle gaps named by
the engine's own spans, on a hand-built trace with known answers."""
import json
from pathlib import Path

import pytest

from bench import spans, trace

D0 = "/device:TPU:0"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE
ROOT = Path(__file__).resolve().parents[2]
US = 1000      # the hand-built trace counts in microseconds


def source(tmp_path, host, device, name="t.json"):
    p = tmp_path / name
    p.write_text(json.dumps({"host": host, "device": device}))
    return spans.JsonSource(str(p))


def us(rows):
    return [[*r[:-2], r[-2] * US, r[-1] * US] for r in rows]


# window [0, 1000) us.  Three programs; the first holds an idle gap
# [100, 150) between its ops.  Outside programs the device idles over
# [300, 500), while the accelerator worker fetches and completes batch 1
# and the offload tier waits on its own device, and over [700, 900), while
# the worker waits for work.
HOST = us([
    ["bench.window", 0, 1000],
    ["bench.npu.fetch", 290, 140],
    ["windve.npu.stage", -20, 20],           # starts before the window
    ["windve.npu.fetch", 300, 120],
    ["windve.npu.ready", 300, 10],
    ["windve.npu.copy", 310, 110],
    ["windve.npu.complete", 420, 50],
    ["windve.npu.hooks", 470, 10],
    ["windve.npu.pop", 480, 10],
    ["windve.npu.stage", 490, 10],
    ["windve.npu.tokenize", 490, 5],
    ["windve.npu.dispatch", 495, 5],
    ["windve.npu.wait", 700, 180],
    ["windve.npu.pop", 880, 5],
    ["windve.npu.stage", 885, 15],
    ["windve.cpu.fetch", 240, 260],
    ["windve.cpu.ready", 250, 230],
    ["windve.cpu.wait", 700, 200],           # never appended
    ["windve.submit", 320, 1],
])
DEVICE = us([
    [D0, MODS, "jit_local(1)", 0, 300],
    [D0, OPS, "fusion:kOutput bf16[64,96,1024]", 0, 100],
    [D0, OPS, "fusion:kOutput bf16[64,96,1024]", 150, 150],
    [D0, MODS, "jit_local(1)", 500, 200],
    [D0, OPS, "fusion:kOutput bf16[64,96,1024]", 500, 200],
    [D0, MODS, "jit_local(1)", 900, 100],
    [D0, OPS, "fusion:kOutput bf16[64,96,1024]", 900, 100],
])


@pytest.fixture
def hand(tmp_path):
    return source(tmp_path, HOST, DEVICE)


def test_host_bound_idle_leaves_out_programs_and_waits(hand):
    s = spans.program_summary(hand)
    assert s.window_s == pytest.approx(1e-3)
    assert s.idle_s == pytest.approx(450e-6)
    # 400 us idle outside programs, 180 us of it in ``wait``
    assert s.host_bound_idle_s == pytest.approx(220e-6)
    m = s.metrics()
    assert m["host_bound_idle_pct"] == pytest.approx(22.0)
    assert m["host_bound_idle_pct"] <= \
        100 * trace.summarize(hand).idle_share == pytest.approx(45.0)


def test_stage_and_host_time_per_batch(hand):
    m = spans.program_summary(hand).metrics()
    # stages starting in the window: 10 and 15 us
    assert m["npu_stage_p50_ms"] == pytest.approx(12.5e-3)
    # pop 10 + 5, stage 10 + 15, copy 110, complete 50, hooks 10 over two
    # stages; fetch, ready and the children inside stage are not counted
    assert m["npu_host_ms_per_batch"] == pytest.approx(105e-3)


def test_gaps_named_by_the_deepest_span(hand):
    s = spans.program_summary(hand)
    assert s.idle_gaps == [
        ["windve.npu.copy+windve.cpu.ready", pytest.approx(200e-6)],
        ["windve.npu.wait", pytest.approx(200e-6)],
        ["in-program", pytest.approx(50e-6)]]
    assert set(s.idle_by_span) == {"windve.npu.copy+windve.cpu.ready",
                                   "windve.npu.wait", "in-program"}


def test_worker_coverage(hand):
    # fetch..stage over [300, 500), wait..stage over [700, 900)
    assert spans.program_summary(hand).coverage_pct == pytest.approx(40.0)


def test_a_gap_no_span_covers_half_of_takes_the_most(tmp_path):
    host = us([["bench.window", 0, 100],
               ["windve.npu.complete", 10, 30],
               ["windve.npu.hooks", 40, 20],
               ["windve.cpu.stage", 10, 20]])
    device = us([[D0, MODS, "jit_local(1)", 0, 10],
                 [D0, OPS, "fusion:kLoop f32[64]", 0, 10],
                 [D0, MODS, "jit_local(1)", 90, 10],
                 [D0, OPS, "fusion:kLoop f32[64]", 90, 10]])
    s = spans.program_summary(source(tmp_path, host, device))
    assert s.idle_gaps == [["windve.npu.complete", pytest.approx(80e-6)]]
    assert "npu_stage_p50_ms" not in s.metrics()


def test_the_benchmark_reads_its_own_spans_alone(hand):
    """``summarize`` of a recording labels gaps by ``bench.`` spans only,
    as it does on the profiler's own file."""
    s = trace.summarize(hand)
    assert set(s.idle_by_host) == {"in-program", "bench.npu.fetch", "idle"}
    assert all(n.startswith("windve.")
               for n, _, _ in hand.program_events())


def test_record_keeps_a_stretch_around_the_longest_gap(hand, tmp_path):
    out = tmp_path / "rec.json"
    data = spans.record(hand, str(out), around=1)
    # one program either side of the longest gap outside a program
    # ([300, 500) comes first of the two 200 us gaps): [0, 700)
    assert data["host"][0] == [trace.WINDOW, 0, 700 * US]
    again = spans.JsonSource(str(out))
    s = spans.program_summary(again)
    assert s.window_s == pytest.approx(700e-6)
    assert s.idle_gaps[0] == ["windve.npu.copy+windve.cpu.ready",
                              pytest.approx(200e-6)]


def test_traced_run_reads_spans_from_the_summarised_trace(hand, tmp_path,
                                                          monkeypatch):
    """``traced_run`` reads the program spans from the very source the run
    summarises, and puts the benchmark's reader back afterwards."""
    from bench import run

    def run_cell(root, name, seed, seconds, traced, **kw):
        assert trace.XplaneSource is spans.XplaneSource
        return {"summary": trace.summarize(hand)}

    monkeypatch.setattr(run, "run_cell", run_cell)
    rec = tmp_path / "rec.json"
    out, summ = spans.traced_run(ROOT, "bge.ingest", 1, 1.0,
                                 record_to=str(rec))
    assert out["summary"].idle_share == pytest.approx(0.45)
    assert summ.metrics()["host_bound_idle_pct"] == pytest.approx(22.0)
    assert rec.stat().st_size < spans.RECORD_BYTES
    assert trace.XplaneSource is not spans.XplaneSource
    assert trace.summarize.__name__ == "summarize"


def test_cell_suffix():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spans.cell_suffix(bench, "bge.query_steady") == "query"
    assert spans.cell_suffix(bench, "bge.ingest") == "ingest"


def test_span_cost_is_measured():
    cost = spans.span_cost(200)
    assert set(cost) == {"trace_annotation", "trace_annotation_kw",
                         "host_span", "host_span_batch"}
    assert all(v > 0 for v in cost.values())


RECORDED = ROOT / "bench" / "tests" / "data" / "trace_bge_ingest_v5e.json"


@pytest.fixture(scope="module")
def recorded():
    """Six executions of the (64, 96) bge-large step on a TPU v5e in the
    ingestion cell, around its longest idle gap, with both tiers' program
    spans (recorded with ``python3 -m bench.spans --record``)."""
    return spans.JsonSource(str(RECORDED))


def test_recorded_ingest_trace(recorded):
    s = spans.program_summary(recorded)
    m = s.metrics()
    assert s.window_s == pytest.approx(0.3253573, rel=1e-5)
    assert len(s.stage_s) == 6
    assert m["host_bound_idle_pct"] == pytest.approx(54.617, abs=1e-2)
    assert m["npu_stage_p50_ms"] == pytest.approx(1.5576, rel=1e-3)
    assert m["npu_host_ms_per_batch"] == pytest.approx(30.484, rel=1e-3)
    assert s.coverage_pct > 99
    # the longest gap: the accelerator worker resolving a batch's futures
    # (the client's callbacks resubmit) while the offload tier's XLA:CPU
    # step runs on the same cores
    assert s.idle_gaps[0] == ["windve.npu.complete+windve.cpu.ready",
                              pytest.approx(0.1321390, rel=1e-5)]
    assert all(n == "in-program" or n.startswith("windve.npu.")
               for n, _ in s.idle_gaps)
    names = {n for n, _, _ in recorded.program_events()}
    assert {"windve.npu.stage", "windve.cpu.ready", "windve.submit"} <= names


def test_host_bound_idle_never_exceeds_device_idle(hand, recorded):
    for src in (hand, recorded):
        host_bound = spans.program_summary(src).metrics()[
            "host_bound_idle_pct"]
        assert 0 < host_bound <= 100 * trace.summarize(src).idle_share


def test_recording_is_small():
    assert RECORDED.stat().st_size < 500_000
