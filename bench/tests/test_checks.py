"""``correct`` comes out false when the timed path is broken underneath.

The harness runs on the CPU at the small width, with its look for a chip
skipped, and a fault of ``bench/faults.py`` planted in the program's
backends after the engine is built: each of the faults an embedding cell
can have.  (A served encoder keeps no state between steps and the cells
run on one chip, so "a step that returns its state unchanged" and "the
exchange between chips left out" have no place here.)
"""
import numpy as np
import pytest

from bench import run
from bench.faults import FAULTS

SEED = 2 ** 31 + 101
CELLS = ["bge.query_steady", "bge.ingest"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered",
                                   "tokens_shuffled"], ids=str)
def test_a_broken_timed_path_is_not_correct(bench_root, small_program, cell,
                                            fault):
    out = run.run_cell(bench_root, cell, SEED, 1.0, False,
                       require_tpu=False, fault=FAULTS[fault])
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["check"].values())


def test_the_unbroken_path_is_correct(bench_root, small_program):
    out = run.run_cell(bench_root, "bge.ingest", SEED, 1.0, False,
                       require_tpu=False)
    assert out["correct"] is True
    assert all(np.isfinite(c["value"]) for c in out["check"].values())
