"""The control: the program's own int8 serving path, one precision below
the configurations' bf16, run through the whole harness, must come out not
correct, where bf16 on the same seed comes out correct."""
import pytest

from bench import run

SEED = 2 ** 31 + 303


@pytest.mark.parametrize("cell", ["bge.query_steady", "bge.ingest"])
def test_int8_serving_is_not_correct(bench_root, small_program, cell):
    ok = run.run_cell(bench_root, cell, SEED, 1.0, False, require_tpu=False)
    low = run.run_cell(bench_root, cell, SEED, 1.0, False,
                       require_tpu=False, precision="int8")
    assert ok["correct"] is True, ok["check"]
    assert low["correct"] is False, low["check"]
    assert low["check"]["bias"]["value"] > low["check"]["bias"]["limit"]
