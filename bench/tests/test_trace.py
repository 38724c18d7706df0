"""Trace reduction: busy union, idle share, op and kernel time, and idle
gaps named by the host span that overlaps them."""
import json

import pytest

from bench import trace

D0, D1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


def source(tmp_path, host, device):
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"host": host, "device": device}))
    return trace.JsonSource(str(p))


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert trace.union_length(iv) == 26
    assert trace.gaps(iv, 0, 40) == [(15, 20), (31, 40)]
    assert trace.gaps(iv, -5, 25) == [(-5, 0), (15, 20)]


FUSION = ("%fusion.{i} = bf16[64,96,1024]{{2,1,0:T(8,128)(2,1)S(1)}} "
          "fusion(bf16[64,96,1024]{{2,1,0:T(8,128)(2,1)S(1)}} %p.1), "
          "kind=kOutput, calls=%fused_computation.{i}")
POOL = ("%branch_0_fun.1 = f32[64,1024]{1,0:T(8,128)} custom-call("
        "bf16[64,96,1024]{2,1,0:T(8,128)(2,1)S(1)} %fusion.82, "
        "f32[64,96]{1,0:T(8,128)S(1)} %get-tuple-element.294), "
        'custom_call_target="tpu_custom_call", '
        "operand_layout_constraints={bf16[64,96,1024]{2,1,0}, f32[64,96]{1,0}}")
WHILE = ("%while.21 = (s32[]{:T(128)}, bf16[64,96,1024]{2,1,0:T(8,128)}) "
         "while((s32[]{:T(128)}, bf16[64,96,1024]{2,1,0}) %tuple.34), "
         "condition=%c, body=%b")


def test_op_labels_keep_what_is_stable():
    assert trace.op_label(FUSION.format(i=7)) == \
        "fusion:kOutput bf16[64,96,1024]"
    assert trace.op_label(POOL) == \
        "custom-call:tpu_custom_call f32[64,1024] <- bf16[64,96,1024]"
    assert trace.op_label(WHILE) == "while (s32[], bf16[64,96,1024])"
    assert trace.op_label("plain") == "plain"


def test_summary_clips_to_the_window(tmp_path):
    host = [["bench.window", 100, 100],            # window [100, 200)
            ["bench.npu.stage", 135, 35],
            ["bench.submit", 185, 5]]
    device = [
        [D0, OPS, FUSION.format(i=1), 90, 30],     # 20 inside
        [D0, OPS, FUSION.format(i=2), 125, 5],
        [D0, OPS, WHILE, 90, 40],                  # a container: not busy
        [D0, OPS, POOL, 175, 5],
        [D0, MODS, "jit_local(1)", 95, 35],        # 30 inside
        [D0, MODS, "jit_local(2)", 174, 7],
        [D0, "Steps", "ignored", 100, 100],
        [D0, OPS, FUSION.format(i=1), 250, 10],    # after the window
    ]
    s = trace.summarize(source(tmp_path, host, device))
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(30e-9)
    assert s.program_s == pytest.approx(37e-9)
    assert s.idle_share == pytest.approx(0.70)
    assert s.ops_matching(r"^fusion") == (pytest.approx(25e-9), 2)
    assert s.ops_matching(r"^custom-call") == (pytest.approx(5e-9), 1)
    assert s.modules_matching(r"^jit_local\(") == (pytest.approx(37e-9), 2)
    # gaps: [120, 125) inside jit_local(1); [130, 175) mostly staging;
    # [180, 200) the submit span covers a quarter of, and nothing else
    assert s.idle_gaps[0] == ["bench.npu.stage", pytest.approx(45e-9)]
    assert s.idle_by_host == {"in-program": pytest.approx(5e-9),
                              "bench.npu.stage": pytest.approx(45e-9),
                              "bench.submit": pytest.approx(20e-9)}
    assert s.top_ops(1) == [["fusion:kOutput bf16[64,96,1024]",
                             pytest.approx(25e-9)]]


def test_recorded_v5e_trace(tmp_path):
    """Two executions of the (64, 96) jina-v2 step on a TPU v5e, with the
    host spans around them (recorded, trimmed to the two executions)."""
    from pathlib import Path

    s = trace.summarize(trace.JsonSource(
        str(Path(__file__).parent / "data" / "trace_jina_v5e.json")))
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.0994594, rel=1e-5)
    assert s.modules_matching(r"^jit_local\(") == (
        pytest.approx(0.0493688, rel=1e-5), 2)
    # ops fill the programs but for a few microseconds between them
    assert s.busy_s == pytest.approx(s.program_s, rel=1e-3)
    assert s.idle_share == pytest.approx(0.5036, abs=1e-3)
    secs, n = s.ops_matching(
        r"^custom-call:tpu_custom_call f32\[\d+,1024\] <- \w+\[\d+,\d+,1024\]$")
    assert n == 2 and secs == pytest.approx(12.55e-6, rel=1e-3)
    # the device waits on the worker's fetch of the previous batch
    assert s.idle_gaps[0][0] == "bench.npu.fetch"
    assert s.top_ops(1)[0][0] == "fusion:kOutput bf16[64,96,1024]"


def test_busy_is_averaged_over_chips(tmp_path):
    host = [["bench.window", 0, 100]]
    device = [[D0, OPS, "a", 0, 100], [D1, OPS, "a", 0, 50]]
    s = trace.summarize(source(tmp_path, host, device))
    assert s.devices == 2 and s.busy_s == pytest.approx(75e-9)


def test_a_trace_without_its_window_or_device_ops_is_refused(tmp_path):
    with pytest.raises(ValueError):
        trace.summarize(source(tmp_path, [], [[D0, OPS, "a", 0, 1]]))
    with pytest.raises(ValueError):
        trace.summarize(source(tmp_path, [["bench.window", 0, 10]], []))
